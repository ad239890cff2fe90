"""A decoder whose layers are of TWO KINDS in a repeating pattern: KDA
linear-attention layers and gated latent-attention (MLA) layers, `period`
layers to a period, over sigmoid-routed experts chosen within groups
(Ling-3.0-flash's language model by config), TPU-first, training only.

Layer i (published index) mixes with MLA when (i + 1) % `period` == 0 and
with KDA otherwise; its second sublayer is a dense SwiGLU (`d_ff`) when
i < `n_dense_layers` and routed + shared experts otherwise. All pre-norm,
over the layer library: `models/blocks.py` (RMSNorm, the SwiGLU sublayer,
remat, the loss), `models/mixers.py` (MLA, and KDA in the form this
config's gate fields give), `models/experts.py` (the routed block) and
`models/layer_pattern.py` (the plan and its walk). With h = RMSNorm(x), per
head (H heads, d = `kda_head_dim` 128):

- *KDA* (Kimi Delta Attention, arXiv:2510.26692; `ops/kda.py`): q~, k~, v~ =
  W_q h, W_k h, W_v h; each channel through a causal depthwise conv over
  time of `conv_size` 4 taps, then SiLU; q = l2norm(q) / sqrt(d), k =
  l2norm(k). Decay: a = W_f h + dt_bias (full rank), g = `kda_lower_bound`
  x sigmoid(exp(A_log_head) a) in (-5, 0) per channel, alpha = exp(g):
  `ops/kda.py` is told the bound and takes its bounded plan. The sublayer
  (`mixers.kda_sublayer`) and the op take other forms as well (no lower
  bound, low-rank gates, beta in (0, 2): `models/solar_open2.py`); this
  module's published configurations have none of them and its config
  refuses them. beta = sigmoid(w_b . h). State S in R^{d x d}, float32, S_0 = 0:
      S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t
  x = x + W_o [RMSNorm_head(o_t) * sigmoid(W_g h)]. No RoPE.
- *MLA*: `mixers.mla_sublayer` with q = W_q h directly (no q latent),
  RMSNorm per head on q and k a part at a time (the 128 score channels,
  the 64 rotary ones), RoPE on the rotary channels, pairs (2i, 2i + 1)
  with `rope_interleave` (the language model's published value), and
  attn_head * sigmoid(w_gate,head . h) before W_o.
- *experts*: sigmoid scores s; the choice on s + bias (no gradient) within
  groups: a group's score is the sum of its two best biased scores, the
  best `topk_group` of `n_group` groups stay, top-k among their experts;
  weights the unbiased s of the chosen, normalised, x
  `routed_scaling_factor`; plus the shared expert (`parallel/moe.route`).
  A nonzero SwiGLU limit (`expert_swiglu_limits`,
  `shared_swiglu_limits`, per published layer) in a held layer raises: its
  form is not published.

`layers` lists the published indices this program holds, in order (all of
`n_layers_published` by default: the whole model). `layer_pattern.walk` runs
them: the expert layers that fill whole ALIGNED periods (indices p * period
.. p * period + period - 1, none of them dense) as one scan, a period its
stacked KDA layers and then its MLA layer; the others (dense layers; expert
layers of a period that is not whole here, published 2-5) unrolled. Remat
is per layer, the flash call's `o` and `lse` and the KDA call's `o` saved.

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


@dataclasses.dataclass(frozen=True)
class HybridMoeConfig(experts.Share):
    """`layers`: the published indices held here (None: all). `d_ff` is the
    dense layers' width, `d_ff_expert` ONE expert's. The MLA and expert
    fields carry `mla_moe.MlaMoeConfig`'s names: the same sublayers
    (`mixers.mla_sublayer`, `experts.expert_sublayer`) read them."""
    vocab_size: int = 157_184
    d_model: int = 2560
    n_layers_published: int = 42
    layers: Optional[Tuple[int, ...]] = None
    n_dense_layers: int = 2        # published layers 0 .. n_dense_layers - 1
    period: int = 6                # MLA iff (i + 1) % period == 0
    n_heads: int = 32
    kda_head_dim: int = 128
    conv_size: int = 4
    kda_lower_bound: float = -5.0  # in [-5, 0): `ops/kda.py`'s bounded plan
    kda_gate_rank: int = 0         # `mixers.kda_sublayer` reads both: full
    kda_beta_scale: float = 1.0    # rank and beta in (0, 1) here, fixed
    q_lora_rank: int = 0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_head_norm: bool = True
    attn_gate: bool = True
    d_ff: int = 6144
    d_ff_expert: int = 768
    n_experts: int = 512
    n_experts_held: int = 512
    first_expert: int = 0
    experts_per_token: int = 8
    n_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    expert_swiglu_limits: Optional[Tuple[float, ...]] = None
    shared_swiglu_limits: Optional[Tuple[float, ...]] = None
    rope_theta: float = 6_000_000.0
    rope_interleave: bool = True
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "dots"
    loss_chunk_size: int = 0
    score = "sigmoid"              # `experts.routing` reads it

    def __post_init__(self):
        for name in ("layers", "expert_swiglu_limits",
                     "shared_swiglu_limits"):
            v = getattr(self, name)
            if v is not None and not isinstance(v, tuple):
                object.__setattr__(self, name, tuple(v))
        held = self.held_layers
        self.held  # raises where the share is outside the router's outputs
        if (self.kda_lower_bound is None
                or not -5.0 <= self.kda_lower_bound < 0
                or self.kda_gate_rank or self.kda_beta_scale != 1.0):
            raise ValueError(
                "this module's KDA gate is Ling's published form: "
                "`kda_lower_bound` in [-5, 0) (`ops/kda.py`'s bounded plan), "
                "full-rank gates, beta in (0, 1). `mixers.kda_sublayer` and "
                "`ops/kda.py` take a gate with no bound, low-rank gates and "
                "beta in (0, 2) (`models/solar_open2.py`), but no published "
                "configuration of this module has them and "
                "`benchmarks/reference_ling.py` does not either")
        for i in held:
            if i < self.n_dense_layers and self.is_mla(i):
                raise NotImplementedError("a dense layer that mixes with MLA")
            for name in ("expert_swiglu_limits", "shared_swiglu_limits"):
                limits = getattr(self, name)
                if limits and i >= self.n_dense_layers and limits[i]:
                    raise NotImplementedError(
                        f"{name}[{i}] = {limits[i]}: the clamped SwiGLU's "
                        "form is not published; hold layers whose limit is 0")

    @staticmethod
    def tiny(vocab_size: int = 512, **over) -> "HybridMoeConfig":
        return HybridMoeConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, n_layers_published=12,
            n_dense_layers=2, period=3, n_heads=4, kda_head_dim=16,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, d_ff=128, d_ff_expert=32, n_experts=16,
            n_experts_held=16, experts_per_token=4, n_group=4, topk_group=2),
            **over})

    @property
    def held_layers(self) -> Tuple[int, ...]:
        return layer_pattern.held_layers(self.layers,
                                         self.n_layers_published)

    def is_mla(self, i: int) -> bool:
        return (i + 1) % self.period == 0

    def plan(self):
        """-> (dense, loose, periods): the held layers' published indices,
        split into the dense ones, the expert layers that run unrolled and
        the first indices of the whole aligned periods, each in order; and
        the execution order as segments ("dense", n), ("loose", n),
        ("periods", n) of consecutive layers / periods."""
        return layer_pattern.segments(
            self.held_layers, self.n_dense_layers, self.period)

    def num_params(self) -> int:
        c = self
        d = c.d_model
        kda = mixers.kda_num_params(c) + 2 * d
        mla = mixers.mla_num_params(c) + 2 * d
        routed = (d * c.n_experts + c.n_experts + 3 * d * c.d_ff_expert
                  * (c.n_experts_held + c.n_shared_experts))
        total = 2 * c.vocab_size * d + d
        for i in c.held_layers:
            total += mla if c.is_mla(i) else kda
            total += 3 * d * c.d_ff if i < c.n_dense_layers else routed
        return total


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def param_logical_axes(config: HybridMoeConfig) -> Dict[str, Any]:
    c = config
    dense, loose, periods, _ = c.plan()
    L = ("layers",)
    axes = {"embed": ("vocab", "embed"), "final_norm": (None,),
            "lm_head": ("embed", "vocab")}
    if dense:
        axes["dense"] = {**mixers.kda_axes(c, L), **blocks.ffn_axes(L)}
    for name, mla in (("kda", False), ("mla", True)):
        if any(c.is_mla(i) == mla for i in loose):
            axes.setdefault("loose", {})[name] = {
                **(mixers.mla_axes(L, c) if mla else mixers.kda_axes(c, L)),
                **experts.routed_axes(L)}
    if periods:
        axes["periods"] = {
            "kda": {**mixers.kda_axes(c, L + (None,)),
                    **experts.routed_axes(L + (None,))},
            "mla": {**mixers.mla_axes(L, c), **experts.routed_axes(L)}}
    return axes


def init(config: HybridMoeConfig, key) -> Dict[str, Any]:
    """`mla_moe.init`'s rules (the embedding's rows N(0, 1), the router 0.02
    normal, its bias float32 N(0, 0.01^2)) and `_init_kda`'s."""
    c = config
    dense, loose, periods, _ = c.plan()

    def mixer(key, mla):
        return mixers.init_mla(c, key) if mla else mixers.init_kda(c, key)

    def dense_layer(key):
        k_mix, *ks = jax.random.split(key, 4)
        return {**mixer(k_mix, False), **blocks.init_ffn(c, ks, (), c.d_ff)}

    def expert_layer(key, mla):
        k_mix, k_r, k_b, *ks = jax.random.split(key, 9)
        return {**mixer(k_mix, mla), **experts.init_routed(c, k_r, k_b, ks)}

    def stack(key, n, mla):
        return jax.vmap(partial(expert_layer, mla=mla))(
            jax.random.split(key, n))

    k_embed, k_dense, k_loose, k_periods, k_head = jax.random.split(key, 5)
    params = {
        "embed": blocks.dense(c, k_embed, (c.vocab_size, c.d_model), 1),
        "final_norm": jnp.ones((c.d_model,), c.dtype),
        "lm_head": blocks.dense(c, k_head, (c.d_model, c.vocab_size),
                                c.d_model),
    }
    if dense:
        params["dense"] = jax.vmap(dense_layer)(
            jax.random.split(k_dense, len(dense)))
    for j, (name, mla) in enumerate((("kda", False), ("mla", True))):
        n = sum(c.is_mla(i) == mla for i in loose)
        if n:
            params.setdefault("loose", {})[name] = stack(
                jax.random.fold_in(k_loose, j), n, mla)
    if periods:
        k_kda, k_mla = jax.random.split(k_periods)
        params["periods"] = {
            "kda": jax.vmap(lambda k: stack(k, c.period - 1, False))(
                jax.random.split(k_kda, len(periods))),
            "mla": stack(k_mla, len(periods), True)}
    return params


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def layer(x, p, positions, config, mesh, rules, mla: bool, dense: bool):
    """One layer -> (x, the chosen experts [B * S, k] or None)."""
    if mla:
        x = mixers.mla_sublayer(x, p, positions, config, mesh, rules)
    else:
        x = mixers.kda_sublayer(x, p, config, mesh, rules)
    if dense:
        return blocks.mlp_sublayer(x, p, config, mesh, rules), None
    return experts.expert_sublayer(x, p, config, mesh, rules)


def forward_hidden(params, tokens, config: HybridMoeConfig, mesh=None,
                   rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> (final-norm hidden states [B, S, D], the chosen
    experts of every expert layer [L, B * S, k], in the layers' order)."""
    c = config
    x, positions = blocks.embed_tokens(params, tokens, mesh, rules)
    x = residual(x.astype(c.dtype), mesh, rules)
    # remat a layer; the flash call's and the KDA call's outputs saved too
    body = lambda mla, dense=False: blocks.checkpointed(partial(  # noqa: E731
        layer, positions=positions, config=c, mesh=mesh, rules=rules,
        mla=mla, dense=dense), c, FLASH_RESIDUALS + kda_op.RESIDUAL_NAMES)
    dense, loose, _, segments = c.plan()
    x, chosen = layer_pattern.walk(
        x, segments,
        ["dense"] * len(dense) + ["mla" if c.is_mla(i) else "kda"
                                  for i in loose],
        {"dense": params.get("dense"), **params.get("loose", {})},
        params.get("periods"), [("kda", c.period - 1), ("mla", None)],
        {"dense": body(False, dense=True), "kda": body(False),
         "mla": body(True)}.__getitem__)
    return rms_norm(x, params["final_norm"], c.norm_eps), chosen


def loss_fn(params, batch, config: HybridMoeConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """Next-token CE (`blocks.next_token_loss`), masked by batch["mask"]
    when given. Scalar return (make_train_step contract)."""
    return blocks.next_token_loss(forward_hidden, None, params, batch, config,
                                  mesh, rules)
