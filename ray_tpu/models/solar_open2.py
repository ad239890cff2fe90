"""A decoder whose layers are of TWO KINDS in a period that starts with the
full one: a gated softmax GQA layer WITHOUT rotary embedding, then `period`
- 1 KDA linear-attention layers, every layer over sigmoid-routed experts and
a shared expert (Solar-Open2-250B's language model by config), TPU-first,
training only.

Layer i (published index) mixes with GQA when i % `period` == `full_phase`
(Solar: 0, `gqa_layers` [0, 4, .., 44]) and with KDA otherwise; its second
sublayer is the routed + shared experts in every layer (no leading dense
layer, no MTP block). All pre-norm, over the layer library: `models/blocks.py`
(RMSNorm, `attn_sublayer`, remat, the loss), `models/mixers.py` (KDA),
`models/experts.py` (the routed block) and `models/layer_pattern.py` (the
plan and its walk). With h = RMSNorm(x), H heads of d 128:

- *GQA*: `blocks.attn_sublayer` at `n_heads` / `n_kv_heads` heads, no rotary
  embedding (`blocks.Rotary(theta=0)`), causal softmax(q k^T / sqrt(d)) v,
  times sigmoid(W_gate h) a CHANNEL (`blocks.channel_gated`: the layer's
  `w_attn_gate` is [D, H, d]) before W_o.
- *KDA*: `mixers.kda_sublayer` with Kimi Linear's own gate, g = -exp(A_log)
  x softplus(W_f_up W_f_down h + dt_bias): ANY value below 0
  (`kda_lower_bound` None, so `ops/kda.py` takes its any-decay plan), the
  decay's and the output gate's projections through a latent of
  `kda_gate_rank`, beta = 2 x sigmoid(w_b . h) in (0, 2).
- *experts*: sigmoid scores s; the choice is top-k on s + bias (no
  gradient), one group; weights the unbiased s of the chosen, normalised, x
  `routed_scaling_factor`; plus the shared expert (`parallel/moe.route`).

`layers` lists the published indices this program holds, in order (all of
`n_layers_published` by default). `layer_pattern.walk` runs them: those that
fill whole ALIGNED periods (indices `full_phase` + p * period .. + period -
1) as one scan, a period its GQA layer and then its stacked KDA layers; the
others (a held set that starts inside a period) unrolled. Remat is per
layer, the flash call's `o` and `lse` and the KDA call's `o` saved.

The share: `experts.py`'s (`n_experts_held`, `first_expert`).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import blocks, experts, layer_pattern, mixers
from ray_tpu.models.blocks import residual, rms_norm
from ray_tpu.ops import kda as kda_op
from ray_tpu.ops.flash_attention import RESIDUAL_NAMES as FLASH_RESIDUALS
from ray_tpu.parallel.sharding import LogicalAxisRules

_NO_ROPE = blocks.Rotary(theta=0.0)


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config(experts.Share):
    """`layers`: the published indices held here (None: all). `d_ff_expert`
    is ONE expert's width. The KDA and expert fields carry
    `hybrid_moe.HybridMoeConfig`'s names: the same sublayers read them."""
    vocab_size: int = 196_608
    d_model: int = 4096
    n_layers_published: int = 48
    layers: Optional[Tuple[int, ...]] = None
    period: int = 4
    full_phase: int = 0            # GQA iff i % period == full_phase
    n_heads: int = 64              # of both kinds
    n_kv_heads: int = 8
    d_head: int = 128
    kda_head_dim: int = 128
    conv_size: int = 4
    kda_lower_bound: Optional[float] = None  # the softplus gate has none
    kda_gate_rank: int = 128
    kda_beta_scale: float = 2.0
    d_ff_expert: int = 1280
    n_experts: int = 320
    n_experts_held: int = 320
    first_expert: int = 0
    experts_per_token: int = 8
    n_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "dots"
    loss_chunk_size: int = 0
    score = "sigmoid"              # `experts.routing` reads it
    # what `blocks.attn_sublayer` asks of any config
    rope_theta = 0.0
    qk_norm = False
    use_ring_attention = False

    def __post_init__(self):
        if self.layers is not None and not isinstance(self.layers, tuple):
            object.__setattr__(self, "layers", tuple(self.layers))
        self.held_layers
        self.held  # raises where the share is outside the router's outputs
        if not 0 <= self.full_phase < self.period \
                or self.n_heads % self.n_kv_heads:
            raise ValueError("full_phase outside the period, or KV heads "
                             "that do not divide the query heads")

    @staticmethod
    def tiny(vocab_size: int = 512, **over) -> "SolarOpen2Config":
        return SolarOpen2Config(**{**dict(
            vocab_size=vocab_size, d_model=64, n_layers_published=8,
            n_heads=4, n_kv_heads=2, d_head=16, kda_head_dim=16,
            kda_gate_rank=8, d_ff_expert=32, n_experts=16, n_experts_held=16,
            experts_per_token=4), **over})

    @property
    def held_layers(self) -> Tuple[int, ...]:
        return layer_pattern.held_layers(self.layers,
                                         self.n_layers_published)

    def is_full(self, i: int) -> bool:
        return i % self.period == self.full_phase

    def plan(self):
        """`layer_pattern.segments` of the held layers: no dense ones; a
        period starts at its full layer, published index `full_phase` mod
        `period`."""
        return layer_pattern.segments(self.held_layers, 0, self.period,
                                      self.full_phase)

    def num_params(self) -> int:
        c = self
        d = c.d_model
        routed = (d * c.n_experts + c.n_experts + 3 * d * c.d_ff_expert
                  * (c.n_experts_held + c.n_shared_experts))
        total = 2 * c.vocab_size * d + d
        for i in c.held_layers:
            total += (gqa_num_params(c) if c.is_full(i)
                      else mixers.kda_num_params(c)) + 2 * d + routed
        return total


def gqa_num_params(c) -> int:
    """The GQA mixer's parameters (no layer norm): W_q, W_o and the gate a
    channel at the query heads' width, W_k and W_v at the KV heads'."""
    hd = c.n_heads * c.d_head
    return c.d_model * (3 * hd + 2 * c.n_kv_heads * c.d_head)


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _gqa_axes(L):
    return {**blocks.attn_axes(L),
            "w_attn_gate": L + ("embed", "heads", "kv"),
            "mlp_norm": L + (None,)}


def param_logical_axes(config: SolarOpen2Config) -> Dict[str, Any]:
    c = config
    _, loose, periods, _ = c.plan()
    L = ("layers",)
    axes = {"embed": ("vocab", "embed"), "final_norm": (None,),
            "lm_head": ("embed", "vocab")}
    for name, full in (("gqa", True), ("kda", False)):
        if any(c.is_full(i) == full for i in loose):
            axes.setdefault("loose", {})[name] = {
                **(_gqa_axes(L) if full else mixers.kda_axes(c, L)),
                **experts.routed_axes(L)}
    if periods:
        axes["periods"] = {
            "gqa": {**_gqa_axes(L), **experts.routed_axes(L)},
            "kda": {**mixers.kda_axes(c, L + (None,)),
                    **experts.routed_axes(L + (None,))}}
    return axes


def _init_gqa(config, key):
    """One layer's GQA mixer, its gate a channel and its two layer norms:
    fan-in scaled normal matrices, norm scales 1."""
    c = config
    d = c.d_model
    ones = partial(jnp.ones, dtype=c.dtype)
    dense = partial(blocks.dense, c)
    ks = jax.random.split(key, 5)
    return {
        "attn_norm": ones((d,)),
        "wq": dense(ks[0], (d, c.n_heads, c.d_head), d),
        "wk": dense(ks[1], (d, c.n_kv_heads, c.d_head), d),
        "wv": dense(ks[2], (d, c.n_kv_heads, c.d_head), d),
        "wo": dense(ks[3], (c.n_heads, c.d_head, d), c.n_heads * c.d_head),
        "w_attn_gate": dense(ks[4], (d, c.n_heads, c.d_head), d),
        "mlp_norm": ones((d,))}


def init(config: SolarOpen2Config, key) -> Dict[str, Any]:
    """`hybrid_moe.init`'s rules (the embedding's rows N(0, 1), the router
    0.02 normal, its bias float32 N(0, 0.01^2), `mixers.init_kda`'s) and
    `_init_gqa`'s."""
    c = config
    _, loose, periods, _ = c.plan()

    def expert_layer(key, full):
        k_mix, k_r, k_b, *ks = jax.random.split(key, 9)
        mixer = _init_gqa(c, k_mix) if full else mixers.init_kda(c, k_mix)
        return {**mixer, **experts.init_routed(c, k_r, k_b, ks)}

    def stack(key, n, full):
        return jax.vmap(partial(expert_layer, full=full))(
            jax.random.split(key, n))

    k_embed, k_loose, k_periods, k_head = jax.random.split(key, 4)
    params = {
        "embed": blocks.dense(c, k_embed, (c.vocab_size, c.d_model), 1),
        "final_norm": jnp.ones((c.d_model,), c.dtype),
        "lm_head": blocks.dense(c, k_head, (c.d_model, c.vocab_size),
                                c.d_model),
    }
    for j, (name, full) in enumerate((("gqa", True), ("kda", False))):
        n = sum(c.is_full(i) == full for i in loose)
        if n:
            params.setdefault("loose", {})[name] = stack(
                jax.random.fold_in(k_loose, j), n, full)
    if periods:
        k_gqa, k_kda = jax.random.split(k_periods)
        params["periods"] = {
            "gqa": stack(k_gqa, len(periods), True),
            "kda": jax.vmap(lambda k: stack(k, c.period - 1, False))(
                jax.random.split(k_kda, len(periods)))}
    return params


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def layer(x, p, positions, config, mesh, rules, full: bool):
    """One layer -> (x, the chosen experts [B * S, k])."""
    if full:
        x = blocks.attn_sublayer(x, p, positions, config, mesh, rules,
                                 rotary=_NO_ROPE)
    else:
        x = mixers.kda_sublayer(x, p, config, mesh, rules)
    return experts.expert_sublayer(x, p, config, mesh, rules)


def forward_hidden(params, tokens, config: SolarOpen2Config, mesh=None,
                   rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> (final-norm hidden states [B, S, D], the chosen
    experts of every layer [L, B * S, k], in the layers' order)."""
    c = config
    x, positions = blocks.embed_tokens(params, tokens, mesh, rules)
    x = residual(x.astype(c.dtype), mesh, rules)
    # remat a layer; the flash call's and the KDA call's outputs saved too
    body = lambda full: blocks.checkpointed(partial(  # noqa: E731
        layer, positions=positions, config=c, mesh=mesh, rules=rules,
        full=full), c, FLASH_RESIDUALS + kda_op.RESIDUAL_NAMES)
    _, loose, _, segments = c.plan()
    x, chosen = layer_pattern.walk(
        x, segments, ["gqa" if c.is_full(i) else "kda" for i in loose],
        params.get("loose", {}), params.get("periods"),
        [("gqa", None), ("kda", c.period - 1)],
        {"gqa": body(True), "kda": body(False)}.__getitem__)
    return rms_norm(x, params["final_norm"], c.norm_eps), chosen


def loss_fn(params, batch, config: SolarOpen2Config, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """Next-token CE (`blocks.next_token_loss`), masked by batch["mask"]
    when given. Scalar return (make_train_step contract)."""
    return blocks.next_token_loss(forward_hidden, None, params, batch, config,
                                  mesh, rules)
