"""Logical-axis sharding rules (t5x/flax-style, re-implemented).

Model code annotates arrays with LOGICAL axis names ("batch", "seq", "embed",
"heads", "mlp", "vocab", "kv", "expert", "layers"); a rule table maps logical
names to physical mesh axes. This is the Megatron-style TP + FSDP layer the
reference has no native equivalent of (SURVEY.md §5): XLA inserts the
all-gathers/reduce-scatters implied by the shardings.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple, Union

PhysicalAxes = Union[None, str, Tuple[str, ...]]

# Default rule table: logical axis -> mesh axis (or tuple). dp x fsdp x tp.
# Parameter axes ("embed", "heads", ...) and activation axes ("act_*") are
# distinct namespaces: under FSDP the parameter embed dim shards over `fsdp`
# while the activation batch dim also uses `fsdp` — a single array may not
# map one mesh axis twice, so activations never reuse parameter rules.
DEFAULT_RULES: List[Tuple[str, PhysicalAxes]] = [
    # activations
    # batch over ALL data-parallel axes, incl. the inter-slice `dcn` axis
    # of multi-slice meshes (absent/size-1 axes drop out, so single-slice
    # meshes are unaffected). Only dp crosses DCN: its one gradient
    # all-reduce per step lowers hierarchically (ICI reduce-scatter ->
    # DCN all-reduce -> ICI all-gather); model axes stay on ICI.
    ("batch", ("dcn", "dp", "fsdp")),
    ("seq", "sp"),               # sequence/context parallel
    # The residual stream BETWEEN sublayers: its sequence dim also takes
    # `tp` (Megatron's sequence parallelism). A row-parallel matmul (wo,
    # w_down) then ends in a reduce-scatter and a column-parallel one
    # (q/k/v, gate/up) begins with an all-gather the compiler can run
    # beside it, where a whole-sequence residual needs an all-reduce that
    # nothing overlaps (PERF.md §6, PR 30).
    ("res_seq", ("sp", "tp")),
    ("act_embed", None),         # activations: embed replicated
    ("act_heads", "tp"),         # attention activations: heads over TP
    ("act_kv", None),
    ("act_mlp", "tp"),           # MLP activations: hidden over TP
    ("act_vocab", "tp"),         # logits: vocab over TP
    # parameters
    ("embed", "fsdp"),           # params: embed dim sharded for FSDP
    ("heads", "tp"),             # attention heads: tensor parallel
    ("kv", None),                # per-head dim: replicated
    ("mlp", "tp"),               # MLP hidden: tensor parallel
    ("vocab", "tp"),             # vocab dim: tensor parallel
    ("expert", "ep"),            # MoE experts
    ("layers", None),            # scanned layer dim: replicated (pp handles)
    ("stage", "pp"),             # pipeline stage dim
]


class LogicalAxisRules:
    def __init__(self, rules: Optional[Sequence[Tuple[str, PhysicalAxes]]] = None):
        self._rules: Dict[str, PhysicalAxes] = dict(rules if rules is not None else DEFAULT_RULES)

    def to_physical(self, logical_axes: Sequence[Optional[str]], mesh=None,
                    shape: Optional[Sequence[int]] = None):
        """Map logical axis names to a PartitionSpec, dropping mesh axes of
        size 1 (so the same model code runs on any mesh shape) and, given
        the array's `shape`, mesh axes that would not divide their dim
        evenly (a decode step's S = 1, an odd test length)."""
        from jax.sharding import PartitionSpec

        sizes = dict(mesh.shape) if mesh is not None else None

        def resolve(i: int, name: Optional[str]):
            if name is None:
                return None
            phys = self._rules.get(name)
            if phys is None:
                return None
            axes = (phys,) if isinstance(phys, str) else tuple(phys)
            if sizes is not None:
                axes = tuple(a for a in axes if sizes.get(a, 1) > 1)
                if shape is not None:
                    kept, shards = [], 1
                    for a in axes:
                        if shape[i] % (shards * sizes[a]) == 0:
                            kept.append(a)
                            shards *= sizes[a]
                    axes = tuple(kept)
            if not axes:
                return None
            return axes if len(axes) > 1 else axes[0]

        return PartitionSpec(
            *[resolve(i, n) for i, n in enumerate(logical_axes)])

    def replace(self, **kwargs: PhysicalAxes) -> "LogicalAxisRules":
        new = LogicalAxisRules(list(self._rules.items()))
        new._rules.update(kwargs)
        return new


def logical_sharding(mesh, logical_axes: Sequence[Optional[str]],
                     rules: Optional[LogicalAxisRules] = None,
                     shape: Optional[Sequence[int]] = None):
    from jax.sharding import NamedSharding

    rules = rules or LogicalAxisRules()
    return NamedSharding(mesh, rules.to_physical(logical_axes, mesh, shape))


def with_logical_constraint(x, logical_axes: Sequence[Optional[str]],
                            mesh=None, rules: Optional[LogicalAxisRules] = None):
    """Annotate an intermediate value inside jit with a logical sharding;
    each dim keeps only the mesh axes that divide it."""
    import jax

    if mesh is None:
        # Ambient mesh from a `jax.set_mesh(...)` scope; none means
        # the caller is running unsharded.
        mesh = jax.sharding.get_abstract_mesh()
        if mesh.empty:
            return x
    rules = rules or LogicalAxisRules()
    return jax.lax.with_sharding_constraint(
        x, logical_sharding(mesh, logical_axes, rules, x.shape)
    )


def shard_params(params, param_logical_axes, mesh,
                 rules: Optional[LogicalAxisRules] = None):
    """device_put a parameter pytree according to per-leaf logical axes.

    `param_logical_axes` is a matching pytree whose leaves are tuples of
    logical axis names (or None for replicated).
    """
    import jax

    rules = rules or LogicalAxisRules()

    def place(x, axes):
        sharding = logical_sharding(mesh, axes if axes is not None else [None] * x.ndim, rules)
        return jax.device_put(x, sharding)

    return jax.tree.map(place, params, param_logical_axes,
                        is_leaf=lambda x: x is None)


def param_shardings(param_logical_axes, mesh, rules=None):
    """Pytree of NamedShardings from a pytree of logical-axes tuples."""
    rules = rules or LogicalAxisRules()

    def make(axes):
        return logical_sharding(mesh, axes if axes is not None else [], rules)

    import jax

    return jax.tree.map(
        make, param_logical_axes,
        is_leaf=lambda x: x is None or (isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x)),
    )
