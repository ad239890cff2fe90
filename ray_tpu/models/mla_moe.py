"""Latent attention + sigmoid-routed experts: the DeepSeek-V3 layer form
(JoyAI-LLM-Flash by config), TPU-first.

JoyAI's model module, over the layer library (`models/blocks.py`: RMSNorm,
RoPE, the SwiGLU sublayer, remat, the chunked cross-entropy and the MTP
loss; `models/mixers.py`: MLA; `models/experts.py`: the routed block). A
decoder of three kinds of block, all pre-norm:

- *MLA* in every layer: q through a `q_lora_rank` latent, k and v through a
  `kv_lora_rank` latent (each RMS-normed), per head a 128-wide "nope" part
  and a 64-wide rotary part; ONE rotary key shared by all heads. Scores run
  over 128 + 64 = 192 channels, values over `v_head_dim` 128: one
  `flash_attention` call IN PARTS, q and k [B, S, H, 128], the rotary q
  [B, S, H, 64], the rotary key [B, S, 1, 64] and v [B, S, H, 128], each a
  projection's own output (`mixers.mla_sublayer`). RoPE pairs channels
  (2i, 2i + 1) with `rope_interleave` (a fixed permutation of the 64
  against `blocks.rope`'s halves, applied to the WEIGHTS that make the
  rotary q and k, alike, so the scores are the interleaved ones).
- `n_dense_layers` leading layers with a dense SwiGLU of `d_ff`; then expert
  layers: sigmoid scores over `n_experts`, top-k of score + a per-expert
  bias that gets no gradient (`noaux_tc`), weights normalised over the k
  chosen and scaled by `routed_scaling_factor`, routed SwiGLU experts of
  `d_ff_expert` through `parallel/moe.moe_layer`, plus a shared expert
  every token passes. No auxiliary loss.
- an MTP block (`mtp_depth` 1): [RMSNorm(Emb(t_{i+1})) | RMSNorm(h_i)] W_eh,
  one expert layer of its own, its own final norm, the SHARED embedding and
  lm_head, predicting t_{i+2}; loss = CE + `mtp_loss_coef` * CE_mtp.

With `hc_mult` n > 0 the residual path is n streams (`models/streams.py`,
Xing4.0 by config): the embedding is copied into the n, each sublayer reads
a learned mix of them and its output is spread over them by another, with a
Sinkhorn-normalised n x n map on the streams themselves, two connections a
layer; the final norm (and the MTP block's, after its own n streams) takes
their sum. With `rope_scaling` (the published YaRN group) the 64 rotary
channels turn at the blended frequencies and the scores are scaled by
DeepSeek's `mscale`. 0 and None: the plain path above, the same program
as before these fields were.

The share: with `n_experts_held` < `n_experts` this program is one chip of
an expert-parallel deployment run without its exchange: the router keeps
all `n_experts` outputs, the layer computes the pairs whose expert is in
[`first_expert`, `first_expert + n_experts_held`) and the shared expert;
what the absent experts would add is left out (`moe_layer`'s `held`).
"""


from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu._private import device_profiler
from ray_tpu.models import blocks, experts, streams
from ray_tpu.models.blocks import checkpointed, residual, rms_norm
from ray_tpu.models.mixers import (
    init_mla, mla_axes, mla_mixer, mla_num_params, mla_sublayer)
from ray_tpu.parallel.sharding import LogicalAxisRules



@dataclasses.dataclass(frozen=True)
class MlaMoeConfig(experts.Share):
    """`n_layers` counts the dense and the expert layers, not the MTP
    block. `d_ff` is the dense layers' width, `d_ff_expert` ONE expert's."""
    vocab_size: int = 129_280
    d_model: int = 2048
    n_layers: int = 40
    n_dense_layers: int = 1
    n_heads: int = 32
    q_lora_rank: int = 1536        # 0: q = W_q x, no latent (`wq`)
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 7168
    d_ff_expert: int = 768
    n_experts: int = 256           # the router's outputs
    n_experts_held: int = 256      # of them, the experts this program holds
    first_expert: int = 0
    experts_per_token: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    rope_theta: float = 32_000_000.0
    rope_interleave: bool = True
    # the published YaRN group: `factor`, `original_max_position_embeddings`,
    # `beta_fast`, `beta_slow`, `mscale`, `mscale_all_dim` (None: plain RoPE)
    rope_scaling: Any = None
    # the residual path: 0, one stream; n, `models/streams.py`'s n
    hc_mult: int = 0
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    h_res_clamp_min: float = -30.0   # on H_res's logits, before exp
    h_res_clamp_max: float = 30.0
    # per-head RMSNorm of q and k before RoPE, a part at a time
    qk_head_norm: bool = False
    attn_gate: bool = False        # attn_h * sigmoid(w_h . x) before W_o
    n_group: int = 1               # the choice within groups (`moe.route`)
    topk_group: int = 1
    norm_eps: float = 1e-6
    mtp_depth: int = 1
    mtp_loss_coef: float = 0.1
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "dots"
    loss_chunk_size: int = 0
    # what `experts.routing` also reads of its config: a constant here
    score = "sigmoid"

    def __post_init__(self):
        if self.mtp_depth not in (0, 1):
            raise ValueError("mtp_depth is 0 or 1")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("n_dense_layers outside [0, n_layers]")
        self.held  # raises where the share is outside the router's outputs
        if isinstance(self.rope_scaling, dict):  # hashable, as jit wants
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))

    @property
    def rotary(self) -> Optional[blocks.Rotary]:
        """The rotary parts' form under `rope_scaling`, as HF
        `modeling_deepseek_v3` reads the group: YaRN's blended frequencies,
        cos and sin times mscale(`mscale`) / mscale(`mscale_all_dim`)."""
        if self.rope_scaling is None:
            return None
        g = dict(self.rope_scaling)
        if g.get("type", g.get("rope_type")) != "yarn":
            raise NotImplementedError(f"rope_scaling {g}")
        return blocks.Rotary(
            float(self.rope_theta), None,
            (g["factor"], g["original_max_position_embeddings"],
             g.get("beta_fast", 32), g.get("beta_slow", 1)),
            _mscale(g["factor"], g.get("mscale", 1))
            / _mscale(g["factor"], g.get("mscale_all_dim", 0)))

    @property
    def attn_scale(self) -> Optional[float]:
        """The scores' scale under `rope_scaling`: (128 + 64) ** -0.5 times
        mscale(`mscale_all_dim`) ** 2; None: the kernels' own."""
        if self.rope_scaling is None:
            return None
        g = dict(self.rope_scaling)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 \
            * _mscale(g["factor"], g.get("mscale_all_dim", 0)) ** 2

    @staticmethod
    def tiny(vocab_size: int = 512, **over) -> "MlaMoeConfig":
        return MlaMoeConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, n_layers=3, n_dense_layers=1,
            n_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, d_ff=128, d_ff_expert=32,
            n_experts=16, n_experts_held=16, experts_per_token=4,
            max_seq_len=128), **over})

    def num_params(self) -> int:
        c = self
        mla = mla_num_params(c) + 2 * c.d_model + (
            2 * streams.connection_num_params(c.hc_mult, c.d_model)
            if c.hc_mult else 0)
        dense = mla + 3 * c.d_model * c.d_ff
        expert = (mla + c.d_model * c.n_experts + c.n_experts
                  + 3 * c.d_model * c.d_ff_expert
                  * (c.n_experts_held + c.n_shared_experts))
        mtp = c.mtp_depth * (expert + 2 * c.d_model * c.d_model
                             + 3 * c.d_model)
        return (2 * c.vocab_size * c.d_model + c.d_model
                + c.n_dense_layers * dense
                + (c.n_layers - c.n_dense_layers) * expert + mtp)


def _mscale(factor: float, mscale: float) -> float:
    """DeepSeek's `yarn_get_mscale`."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _connection_axes(L, config):
    """The two connections of a layer, where the path has streams."""
    if not config.hc_mult:
        return {}
    return {"hc_attn": streams.connection_axes(L),
            "hc_mlp": streams.connection_axes(L)}


def _init_connections(config, key):
    if not config.hc_mult:
        return {}
    k_attn, k_mlp = jax.random.split(jax.random.fold_in(key, 61))
    return {"hc_attn": streams.init_connection(config, k_attn),
            "hc_mlp": streams.init_connection(config, k_mlp)}


def _expert_layer_axes(L, config):
    return {**mla_axes(L, config), **experts.routed_axes(L),
            **_connection_axes(L, config)}


def param_logical_axes(config: MlaMoeConfig) -> Dict[str, Any]:
    L = ("layers",)
    axes = {
        "embed": ("vocab", "embed"),
        "dense": {**mla_axes(L, config), **blocks.ffn_axes(L),
                  **_connection_axes(L, config)},
        "layers": _expert_layer_axes(L, config),
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }
    if config.mtp_depth:
        axes["mtp"] = {"enorm": (None,), "hnorm": (None,),
                       "eh_proj": (None, "embed"),
                       "block": _expert_layer_axes(L, config),
                       "final_norm": (None,)}
    return axes


def init(config: MlaMoeConfig, key) -> Dict[str, Any]:
    """Fan-in scaled normal weights in `config.dtype`, norm scales 1, the
    router 0.02 normal, the router's bias float32 N(0, 0.01^2): not zero, so
    that it changes choices wherever two scores lie that close; a
    connection's maps as `streams.init_connection` seeds them.

    The embedding's rows are N(0, 1), of unit RMS like every sublayer's
    normed input, and not fan-in scaled (a lookup sums over nothing). With
    rows of RMS d_model ** -0.5 the residual stream of random weights is
    the sublayers' outputs, of which causal attention's is nearly one
    vector for all late positions: every token then ranks the experts much
    alike, an expert's load is anywhere from 0 to several times the even
    one, and how many pairs land on a share's experts is the seed's luck.
    Rows that stand out of the stream keep the tokens apart, so the loads
    are near even at any seed, as a deployment's balancing keeps them."""
    c = config
    ones = partial(jnp.ones, dtype=c.dtype)
    dense = partial(blocks.dense, c)

    def dense_layer(key):
        k_attn, *ks = jax.random.split(key, 4)
        return {**init_mla(c, k_attn), **blocks.init_ffn(c, ks, (), c.d_ff),
                **_init_connections(c, key)}

    def expert_layer(key):
        k_attn, k_r, k_b, *ks = jax.random.split(key, 9)
        return {**init_mla(c, k_attn),
                **experts.init_routed(c, k_r, k_b, ks),
                **_init_connections(c, key)}

    k_embed, k_dense, k_layers, k_head, k_mtp = jax.random.split(key, 5)
    params = {
        "embed": dense(k_embed, (c.vocab_size, c.d_model), 1),
        "dense": jax.vmap(dense_layer)(
            jax.random.split(k_dense, c.n_dense_layers)),
        "layers": jax.vmap(expert_layer)(
            jax.random.split(k_layers, c.n_layers - c.n_dense_layers)),
        "final_norm": ones((c.d_model,)),
        "lm_head": dense(k_head, (c.d_model, c.vocab_size), c.d_model),
    }
    if c.mtp_depth:
        k_proj, k_block = jax.random.split(k_mtp)
        params["mtp"] = {
            "enorm": ones((c.d_model,)), "hnorm": ones((c.d_model,)),
            "eh_proj": dense(k_proj, (2 * c.d_model, c.d_model),
                             2 * c.d_model),
            "block": jax.vmap(expert_layer)(jax.random.split(k_block, 1)),
            "final_norm": ones((c.d_model,)),
        }
    return params


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------

def _mla(x, p, positions, config, mesh, rules):
    """The layer's first sublayer: on one stream `mla_sublayer`; on n
    streams a connection around MLA(RMSNorm(h))."""
    c = config
    if not c.hc_mult:
        return mla_sublayer(x, p, positions, c, mesh, rules, c.rotary,
                            c.attn_scale)

    def branch(h):
        return mla_mixer(rms_norm(h, p["attn_norm"], c.norm_eps), p,
                         positions, c, mesh, c.rotary, c.attn_scale), None

    return streams.connect(x, p["hc_attn"], branch, c, mesh, rules)[0]


def expert_layer(x, p, positions, config, mesh, rules):
    c = config
    x = _mla(x, p, positions, c, mesh, rules)
    if not c.hc_mult:
        return experts.expert_sublayer(x, p, c, mesh, rules)

    def branch(h):
        routed, shared, chosen = experts.expert_parts(
            rms_norm(h, p["mlp_norm"], c.norm_eps), p, c, mesh)
        return routed + shared, chosen

    return streams.connect(x, p["hc_mlp"], branch, c, mesh, rules)


def dense_layer(x, p, positions, config, mesh, rules):
    c = config
    x = _mla(x, p, positions, c, mesh, rules)
    if not c.hc_mult:
        return blocks.mlp_sublayer(x, p, c, mesh, rules)

    def branch(h):
        return blocks.gated_mlp(rms_norm(h, p["mlp_norm"], c.norm_eps), p,
                                c, mesh, rules), None

    return streams.connect(x, p["hc_mlp"], branch, c, mesh, rules)[0]


def _enter(x, config, mesh, rules):
    """The residual path's start: x [B, S, D] as the layers carry it."""
    if config.hc_mult:
        return streams.expand(x, config.hc_mult, mesh, rules)
    return residual(x, mesh, rules)


def _leave(x, config):
    return streams.reduce(x) if config.hc_mult else x


def forward_hidden(params, tokens, config: MlaMoeConfig, mesh=None,
                   rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> (final-norm hidden states [B, S, D], the chosen
    experts of every expert layer [L, B * S, k])."""
    c = config
    x, positions = blocks.embed_tokens(params, tokens, mesh, rules)
    x = _enter(x.astype(c.dtype), c, mesh, rules)
    kw = dict(positions=positions, config=c, mesh=mesh, rules=rules)
    dense = checkpointed(partial(dense_layer, **kw), c)
    x, _ = jax.lax.scan(lambda x, p: (dense(x, p), None), x, params["dense"])
    x, chosen = jax.lax.scan(checkpointed(partial(expert_layer, **kw), c),
                             x, params["layers"])
    return rms_norm(_leave(x, c), params["final_norm"], c.norm_eps), chosen


def mtp_hidden(params, hidden, next_tokens, config: MlaMoeConfig, mesh=None,
               rules: Optional[LogicalAxisRules] = None):
    """The MTP block. hidden [B, S, D]: the main model's final-norm output
    h_i; next_tokens [B, S]: t_{i+1} -> (its own final-norm hidden states
    [B, S, D], from which the shared lm_head predicts t_{i+2}, the block's
    chosen experts [B * S, k])."""
    c = config
    p = params["mtp"]
    b, s = next_tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    with jax.named_scope("mtp.block"):
        emb = blocks.embed_rows(params["embed"], next_tokens,
                                mesh).astype(c.dtype)
        x = jnp.concatenate([rms_norm(emb, p["enorm"], c.norm_eps),
                             rms_norm(hidden, p["hnorm"], c.norm_eps)],
                            axis=-1) @ p["eh_proj"]
        block = checkpointed(partial(
            expert_layer, positions=positions, config=c, mesh=mesh,
            rules=rules), c)
        x, chosen = block(_enter(x, c, mesh, rules),
                          jax.tree.map(lambda a: a[0], p["block"]))
        device_profiler.count("mtp.depth", 1)  # per lowering
        return rms_norm(_leave(x, c), p["final_norm"], c.norm_eps), chosen


def forward(params, tokens, config: MlaMoeConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> next-token logits [B, S, V] float32."""
    x, _ = forward_hidden(params, tokens, config, mesh, rules)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"]).astype(jnp.float32)


def loss_fn(params, batch, config: MlaMoeConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """Next-token CE + `mtp_loss_coef` * the MTP block's CE of the token
    after (`blocks.next_token_loss`). Scalar return."""
    return blocks.next_token_loss(forward_hidden, mtp_hidden, params, batch,
                                  config, mesh, rules)


@partial(jax.jit, static_argnames=("config",))
def routing_stats(params, tokens, config: MlaMoeConfig):
    """tokens [B, S + 1] (as `loss_fn`'s {"tokens": ...}) -> int32
    [expert layers + mtp_depth]: the LIVE rows of each expert layer (the MTP
    block's last), the (token, choice) pairs whose expert is held here.
    Outside the train step, for tests and chip runs."""
    c = config
    inputs, targets, _ = blocks.split_batch({"tokens": tokens})
    hidden, chosen = forward_hidden(params, inputs, c)
    if c.mtp_depth:
        chosen = jnp.concatenate(
            [chosen, mtp_hidden(params, hidden, targets, c)[1][None]])
    return experts.live_rows(chosen, c)


def routing_loads(params, tokens, config: MlaMoeConfig):
    """-> float32, as `routing_stats`: each block's live rows over the rows
    of the capacity it runs at (`experts.capacity_loads`)."""
    return experts.capacity_loads(
        routing_stats(params, tokens, config),
        tokens.shape[0] * (tokens.shape[1] - 1), config)
