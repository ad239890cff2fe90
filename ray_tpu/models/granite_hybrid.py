"""A decoder whose every layer is a mixer AND a dense gated MLP, the mixer
Mamba-2 or GQA attention without a rotary embedding in a published pattern,
every residual branch, the embedding, the scores and the logits under a
published multiplier, the head tied to the embedding (IBM Granite-4.0-H by
config: `model_type` `granitemoehybrid` with no experts), TPU-first,
training only.

As HF `modeling_granitemoehybrid` computes it (RMSNorm eps `norm_eps`):

    x_0 = embedding_multiplier * E[tokens]
    layer i:  x = x + residual_multiplier * mixer_i(RMSNorm(x))
              x = x + residual_multiplier * W_down (silu(W_gate h) * W_up h),
                  h = RMSNorm(x)
    logits = RMSNorm(x_L) E^T / logits_scaling

- `mamba`: `nemotron_h.mamba_mixer` (Mamba-2 through `ops/ssd.py`, walked
  `chunk_size` tokens at a time): at `n_groups` 1 the gated RMSNorm is ONE
  norm over all H x P channels and B and C are shared by all heads.
- `attention`: `llama._attn_sublayer` at `rope_theta` 0 (`nope`), causal,
  softmax(`attention_multiplier` q k^T) v: the multiplier is the scores'
  scale, 1 / 64 at 64-wide heads where d_head ** -0.5 would be 1 / 8.
- the MLP: `llama._mlp_sublayer` (the published `shared_mlp` with its
  [gate | up] projection as two matrices).
- the head IS the embedding: `llama.chunked_ce` on E^T (the fused backward
  pass), so the embedding's gradient is the sum of its two uses.

No multiplier is folded into a weight: a checkpoint's weights do not carry
one. `layers` lists the published indices held here (all by default). Whole
aligned periods of the pattern (`period` layers: nine `mamba` to one
`attention`, published) run as ONE `lax.scan` over the stacked periods whose
body scans each run of `mamba` layers, so two kinds of layer body are traced
whatever the depth; other layers run unrolled. Remat is per layer
(`mla_moe._checkpointed`): under "residuals" a layer keeps its input, the
scan's `y` and the flash call's `o` and `lse`, and recomputes the rest.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import device_profiler
from ray_tpu.models import layer_pattern, llama, mla_moe, nemotron_h
from ray_tpu.models.llama import _residual, _rms_norm
from ray_tpu.ops import ssd as ssd_op
from ray_tpu.ops.flash_attention import RESIDUAL_NAMES as FLASH_RESIDUALS
from ray_tpu.parallel.sharding import LogicalAxisRules, with_logical_constraint

KINDS = ("mamba", "attention")
PUBLISHED_PATTERN = (("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4
_MLP = ("mlp_norm", "w_gate", "w_up", "w_down")


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """`pattern`: a layer's kind by its published index (`layer_types`);
    `layers`: the published indices held here (None: all). The Mamba-2
    fields carry `nemotron_h.NemotronHConfig`'s names and the attention and
    MLP fields `llama.LlamaConfig`'s, whose sublayers read them."""
    vocab_size: int = 100_352
    d_model: int = 2048
    pattern: Tuple[str, ...] = PUBLISHED_PATTERN
    layers: Optional[Tuple[int, ...]] = None
    period: int = 10
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    state_size: int = 128
    n_groups: int = 1
    conv_size: int = 4
    chunk_size: int = 256
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    n_heads: int = 32
    n_kv_heads: int = 8
    d_head: int = 64
    rope_theta: float = 0.0        # 0: `position_embedding_type` "nope"
    d_ff: int = 8192
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    tie_word_embeddings: bool = True
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "residuals"
    loss_chunk_size: int = 0
    # what `llama._attn_sublayer` also reads of its config: constants here
    qk_norm = False
    use_ring_attention = False

    def __post_init__(self):
        for name in ("pattern", "layers"):
            if isinstance(getattr(self, name), list):
                object.__setattr__(self, name, tuple(getattr(self, name)))
        held, kinds = self.held_layers, self.pattern
        if list(held) != sorted(set(held)) or not held \
                or not 0 <= held[0] <= held[-1] < len(kinds):
            raise ValueError(f"layers {held} of {len(kinds)}")
        if set(kinds) - set(KINDS):
            raise ValueError(f"a layer is one of {KINDS}")
        if any(k != kinds[i % self.period] for i, k in enumerate(kinds)):
            raise ValueError(f"the pattern does not repeat every {self.period}")
        if self.mamba_heads % self.n_groups:
            raise ValueError("n_groups does not divide the Mamba heads")
        if not self.tie_word_embeddings:
            raise NotImplementedError("the head is the embedding")

    @staticmethod
    def tiny(vocab_size: int = 512, **over) -> "GraniteHybridConfig":
        return GraniteHybridConfig(**{**dict(
            vocab_size=vocab_size, d_model=64,
            pattern=("mamba", "mamba", "attention", "mamba") * 2, period=4,
            mamba_heads=8, mamba_head_dim=16, state_size=8, n_heads=4,
            n_kv_heads=2, d_head=16, d_ff=96, chunk_size=16,
            embedding_multiplier=3.0, residual_multiplier=0.4,
            attention_multiplier=0.4, logits_scaling=2.0), **over})

    @property
    def held_layers(self) -> Tuple[int, ...]:
        return self.layers if self.layers is not None \
            else tuple(range(len(self.pattern)))

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.state_size

    def plan(self):
        """`layer_pattern.segments` of the held layers (no dense ones):
        (loose, periods, segments)."""
        _, loose, periods, segments = layer_pattern.segments(
            self.held_layers, 0, self.period)
        return loose, periods, segments

    def runs(self):
        """A period as runs of one kind: [(kind, layers), ...]."""
        out = []
        for kind in self.pattern[:self.period]:
            if out and out[-1][0] == kind:
                out[-1][1] += 1
            else:
                out.append([kind, 1])
        return [tuple(r) for r in out]

    def num_params(self) -> int:
        d = self.d_model
        return self.vocab_size * d + d + sum(
            layer_num_params(self, self.pattern[i]) for i in self.held_layers)


def layer_num_params(c, kind: str) -> int:
    """One layer's parameters: its mixer, its MLP and its two norms."""
    d = c.d_model
    mixer = nemotron_h.layer_num_params(c, "M" if kind == "mamba" else "*")
    return mixer + 3 * d * c.d_ff + 2 * d


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _layer_axes(L, kind: str):
    return {**nemotron_h._layer_axes(L, "M" if kind == "mamba" else "*"),
            "mlp_norm": L + (None,), "w_gate": L + ("embed", "mlp"),
            "w_up": L + ("embed", "mlp"), "w_down": L + ("mlp", "embed")}


def _kinds_of(config, indices):
    return {k: sum(config.pattern[i] == k for i in indices) for k in KINDS}


def param_logical_axes(config: GraniteHybridConfig) -> Dict[str, Any]:
    c = config
    loose, periods, _ = c.plan()
    axes = {"embed": ("vocab", "embed"), "final_norm": (None,)}
    if loose:
        axes["loose"] = {k: _layer_axes(("layers",), k)
                         for k, n in _kinds_of(c, loose).items() if n}
    if periods:
        axes["periods"] = {k: _layer_axes(("layers", None), k)
                           for k in KINDS}
    return axes


def _init_layer(config, kind: str, key):
    """`nemotron_h._init_layer`'s mixer (fan-in scaled normal matrices, norm
    scales 1, Mamba-2's own initialisation of `A_log`, `dt_bias` and D) and
    a fan-in scaled MLP."""
    c = config
    k_mix, *ks = jax.random.split(key, 4)
    return {**nemotron_h._init_layer(c, "M" if kind == "mamba" else "*",
                                     k_mix),
            "mlp_norm": jnp.ones((c.d_model,), c.dtype),
            **mla_moe._init_ffn(c, ks, (), c.d_ff)}


def init(config: GraniteHybridConfig, key) -> Dict[str, Any]:
    """The embedding's rows N(0, 1) / `embedding_multiplier`, so that the
    residual stream starts at the unit RMS the other models' embeddings give
    it; the tied head then reads rows of that RMS and its logits are divided
    by `logits_scaling`: small logits, a loss near log V at initialisation."""
    c = config
    loose, periods, _ = c.plan()
    k_embed, k_loose, k_periods = jax.random.split(key, 3)

    def stack(kind, key, *lead):
        fn = partial(_init_layer, c, kind)
        for _ in lead:
            fn = jax.vmap(fn)
        return fn(jax.random.split(key, math.prod(lead)).reshape(
            lead + (-1,)))

    params = {
        "embed": mla_moe._dense(c, k_embed, (c.vocab_size, c.d_model),
                                c.embedding_multiplier ** 2),
        "final_norm": jnp.ones((c.d_model,), c.dtype)}
    if loose:
        params["loose"] = {
            k: stack(k, jax.random.fold_in(k_loose, j), n)
            for j, (k, n) in enumerate(_kinds_of(c, loose).items()) if n}
    if periods:
        per = _kinds_of(c, range(c.period))
        params["periods"] = {
            k: stack(k, jax.random.fold_in(k_periods, j), len(periods), per[k])
            for j, k in enumerate(KINDS)}
    return params


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def _mamba_sublayer(x, p, config, mesh=None, rules=None):
    """x [B, S, D] -> x + residual_multiplier * Mamba-2(RMSNorm(x))."""
    c = config
    h = _rms_norm(x, p["norm"], c.norm_eps)
    out = nemotron_h.mamba_mixer(h, p, c)
    return _residual(x + llama._scaled(out, c.residual_multiplier), mesh,
                     rules)


def _layer(x, p, positions, config, mesh, rules, kind: str):
    """One layer: its mixer, then its MLP, each branch times
    `residual_multiplier`."""
    c = config
    if kind == "mamba":
        x = _mamba_sublayer(x, p, c, mesh, rules)
        device_profiler.count("granite.layers_mamba", 1)  # per lowering
    else:
        x = llama._attn_sublayer(
            x, p, positions, c, mesh, rules, scale=c.attention_multiplier,
            branch=c.residual_multiplier)
        device_profiler.count("granite.layers_attention", 1)
    return llama._mlp_sublayer(x, p, c, mesh, rules,
                               branch=c.residual_multiplier)


def forward_hidden(params, tokens, config: GraniteHybridConfig, mesh=None,
                   rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> final-norm hidden states [B, S, D]."""
    c = config
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    table = with_logical_constraint(params["embed"], ("vocab", "act_embed"),
                                    mesh=mesh, rules=rules)
    x = llama.embed_rows(table, tokens, mesh).astype(c.dtype)
    x = llama._scaled(x, c.embedding_multiplier)
    x = _residual(x, mesh, rules)
    body = {kind: mla_moe._checkpointed(
        partial(_layer, positions=positions, config=c, mesh=mesh,
                rules=rules, kind=kind), c,
        FLASH_RESIDUALS + ssd_op.RESIDUAL_NAMES) for kind in KINDS}
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)  # noqa: E731

    def period(x, p):
        done = dict.fromkeys(KINDS, 0)
        for kind, n in c.runs():
            first = done[kind]
            done[kind] += n
            if n == 1:
                x = body[kind](x, at(p[kind], first))
            else:
                x, _ = jax.lax.scan(
                    lambda x, q, kind=kind: (body[kind](x, q), None), x,
                    jax.tree.map(lambda a: a[first:first + n], p[kind]))
        return x, None

    loose, _, segments = c.plan()
    done = {"loose": 0, "periods": 0, **dict.fromkeys(KINDS, 0)}
    for seg, n in segments:
        first = done[seg]
        done[seg] += n
        if seg == "loose":
            for i in loose[first:first + n]:
                kind = c.pattern[i]
                x = body[kind](x, at(params["loose"][kind], done[kind]))
                done[kind] += 1
            device_profiler.count("pattern.layers_unrolled", n)
        else:
            x, _ = jax.lax.scan(period, x, jax.tree.map(
                lambda a: a[first:first + n], params["periods"]))
            device_profiler.count("pattern.periods", n)  # per lowering
    return _rms_norm(x, params["final_norm"], c.norm_eps)


def forward(params, tokens, config: GraniteHybridConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> next-token logits [B, S, V] float32."""
    x = forward_hidden(params, tokens, config, mesh, rules)
    return jnp.einsum("bsd,vd->bsv", x, params["embed"]).astype(
        jnp.float32) / config.logits_scaling


def loss_fn(params, batch, config: GraniteHybridConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """Next-token CE of RMSNorm(x_L) E^T / `logits_scaling` through
    `llama.chunked_ce`, masked by batch["mask"] when given: the division is
    applied to the hidden states, which a power of two (the published 8)
    scales exactly. Scalar return (make_train_step contract)."""
    c = config
    inputs, targets, mask = mla_moe._split(batch)
    hidden = forward_hidden(params, inputs, c, mesh, rules)
    hidden = llama._scaled(hidden, 1.0 / c.logits_scaling)
    return llama.chunked_ce(hidden, params["embed"].T, targets, mask,
                            chunk=c.loss_chunk_size or inputs.shape[1])
