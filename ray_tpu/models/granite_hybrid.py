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

- `mamba`: `mixers.mamba_sublayer` (Mamba-2 through `ops/ssd.py`, walked
  `chunk_size` tokens at a time): at `n_groups` 1 the gated RMSNorm is ONE
  norm over all H x P channels and B and C are shared by all heads.
- `attention`: `blocks.attn_sublayer` at `rope_theta` 0 (`nope`), causal,
  softmax(`attention_multiplier` q k^T) v: the multiplier is the scores'
  scale, 1 / 64 at 64-wide heads where d_head ** -0.5 would be 1 / 8.
- the MLP: `blocks.mlp_sublayer` (the published `shared_mlp` with its
  [gate | up] projection as two matrices).
- the head IS the embedding: `blocks.chunked_ce` on E^T (the fused backward
  pass), so the embedding's gradient is the sum of its two uses.

No multiplier is folded into a weight: a checkpoint's weights do not carry
one. `layers` lists the published indices held here (all by default).
`layer_pattern.walk` scans the whole aligned periods of the pattern (`period`
layers: nine `mamba` to one `attention`, published), each run of `mamba`
layers a scan inside, and unrolls the other layers. Remat is per layer
(`blocks.checkpointed`): under "residuals" a layer keeps its input, the
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
from ray_tpu.models import blocks, layer_pattern, mixers
from ray_tpu.models.blocks import residual, rms_norm, scaled
from ray_tpu.ops import ssd as ssd_op
from ray_tpu.ops.flash_attention import RESIDUAL_NAMES as FLASH_RESIDUALS
from ray_tpu.parallel.sharding import LogicalAxisRules

KINDS = ("mamba", "attention")
PUBLISHED_PATTERN = (("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """`pattern`: a layer's kind by its published index (`layer_types`);
    `layers`: the published indices held here (None: all). The Mamba-2
    fields carry `nemotron_h.NemotronHConfig`'s names and the attention and
    MLP fields `llama.LlamaConfig`'s: the same sublayers (`mixers.py`,
    `blocks.py`) read them."""
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
    # what `blocks.attn_sublayer` also reads of its config: constants here
    qk_norm = False
    use_ring_attention = False

    def __post_init__(self):
        for name in ("pattern", "layers"):
            if isinstance(getattr(self, name), list):
                object.__setattr__(self, name, tuple(getattr(self, name)))
        kinds = self.pattern
        self.held_layers  # raises where they are not the pattern's, in order
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
        return layer_pattern.held_layers(self.layers, len(self.pattern))

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
    mixer = mixers.mixer_num_params(c, "M" if kind == "mamba" else "*")
    return mixer + 3 * d * c.d_ff + 2 * d


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _layer_axes(L, kind: str):
    return {**mixers.mixer_axes(L, "M" if kind == "mamba" else "*"),
            "mlp_norm": L + (None,), **blocks.ffn_axes(L)}


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
    """`mixers.init_mixer`'s mixer (fan-in scaled normal matrices, norm
    scales 1, Mamba-2's own initialisation of `A_log`, `dt_bias` and D) and
    a fan-in scaled MLP."""
    c = config
    k_mix, *ks = jax.random.split(key, 4)
    return {**mixers.init_mixer(c, "M" if kind == "mamba" else "*", k_mix),
            "mlp_norm": jnp.ones((c.d_model,), c.dtype),
            **blocks.init_ffn(c, ks, (), c.d_ff)}


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
        "embed": blocks.dense(c, k_embed, (c.vocab_size, c.d_model),
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

def layer(x, p, positions, config, mesh, rules, kind: str):
    """One layer: its mixer, then its MLP, each branch times
    `residual_multiplier` -> (x, None: no layer routes)."""
    c = config
    if kind == "mamba":
        x = mixers.mamba_sublayer(x, p, c, mesh, rules,
                                  c.residual_multiplier)
        device_profiler.count("granite.layers_mamba", 1)  # per lowering
    else:
        x = blocks.attn_sublayer(
            x, p, positions, c, mesh, rules, scale=c.attention_multiplier,
            branch=c.residual_multiplier)
        device_profiler.count("granite.layers_attention", 1)
    return blocks.mlp_sublayer(x, p, c, mesh, rules,
                               branch=c.residual_multiplier), None


def forward_hidden(params, tokens, config: GraniteHybridConfig, mesh=None,
                   rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> final-norm hidden states [B, S, D]."""
    c = config
    x, positions = blocks.embed_tokens(params, tokens, mesh, rules)
    x = residual(scaled(x.astype(c.dtype), c.embedding_multiplier), mesh,
                 rules)
    bodies = {kind: blocks.checkpointed(
        partial(layer, positions=positions, config=c, mesh=mesh,
                rules=rules, kind=kind), c,
        FLASH_RESIDUALS + ssd_op.RESIDUAL_NAMES) for kind in KINDS}
    loose, _, segments = c.plan()
    x, _ = layer_pattern.walk(
        x, segments, [c.pattern[i] for i in loose], params.get("loose"),
        params.get("periods"), c.runs(), bodies.__getitem__)
    return rms_norm(x, params["final_norm"], c.norm_eps)


def loss_fn(params, batch, config: GraniteHybridConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """Next-token CE of RMSNorm(x_L) E^T / `logits_scaling` through
    `blocks.chunked_ce`, masked by batch["mask"] when given: the division is
    applied to the hidden states, which a power of two (the published 8)
    scales exactly. Scalar return (make_train_step contract)."""
    c = config
    inputs, targets, mask = blocks.split_batch(batch)
    hidden = forward_hidden(params, inputs, c, mesh, rules)
    hidden = scaled(hidden, 1.0 / c.logits_scaling)
    return blocks.chunked_ce(hidden, params["embed"].T, targets, mask,
                             chunk=c.loss_chunk_size or inputs.shape[1])
