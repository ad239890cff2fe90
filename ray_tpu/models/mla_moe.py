"""Latent attention + sigmoid-routed experts: the DeepSeek-V3 layer form
(JoyAI-LLM-Flash by config), TPU-first.

A decoder of three kinds of block, all pre-norm, sharing `models/llama.py`'s
RMSNorm, RoPE, SwiGLU sublayer, remat policy and chunked cross-entropy:

- *MLA* in every layer: q through a `q_lora_rank` latent, k and v through a
  `kv_lora_rank` latent (each RMS-normed), per head a 128-wide "nope" part
  and a 64-wide rotary part; ONE rotary key shared by all heads. Scores run
  over 128 + 64 = 192 channels, values over `v_head_dim` 128: one
  `flash_attention` call IN PARTS, q and k [B, S, H, 128], the rotary q
  [B, S, H, 64], the rotary key [B, S, 1, 64] and v [B, S, H, 128], each a
  projection's own output (`_mla_sublayer`). RoPE pairs channels
  (2i, 2i + 1) with `rope_interleave` (a fixed permutation of the 64
  against `llama._rope`'s halves, applied to the WEIGHTS that make the
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

The share: with `n_experts_held` < `n_experts` this program is one chip of
an expert-parallel deployment run without its exchange: the router keeps
all `n_experts` outputs, the layer computes the pairs whose expert is in
[`first_expert`, `first_expert + n_experts_held`) and the shared expert;
what the absent experts would add is left out (`moe_layer`'s `held`).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu._private import device_profiler
from ray_tpu.models import llama
from ray_tpu.models.llama import _remat_policy, _residual, _rms_norm, _rope
from ray_tpu.ops.flash_attention import RESIDUAL_NAMES, flash_attention
from ray_tpu.parallel import moe
from ray_tpu.parallel.moe import moe_layer, route
from ray_tpu.parallel.sharding import LogicalAxisRules, with_logical_constraint


@dataclasses.dataclass(frozen=True)
class MlaMoeConfig:
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
    # what `_expert_sublayer` also reads of its config: a constant here
    score = "sigmoid"

    def __post_init__(self):
        if self.mtp_depth not in (0, 1):
            raise ValueError("mtp_depth is 0 or 1")
        if not 0 <= self.n_dense_layers <= self.n_layers:
            raise ValueError("n_dense_layers outside [0, n_layers]")
        if not (0 <= self.first_expert
                and self.first_expert + self.n_experts_held <= self.n_experts):
            raise ValueError("held experts outside the router's outputs")

    @staticmethod
    def tiny(vocab_size: int = 512, **over) -> "MlaMoeConfig":
        return MlaMoeConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, n_layers=3, n_dense_layers=1,
            n_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, d_ff=128, d_ff_expert=32,
            n_experts=16, n_experts_held=16, experts_per_token=4,
            max_seq_len=128), **over})

    @property
    def held(self):
        """`moe_layer`'s `held`: None where every expert is here."""
        if self.n_experts_held == self.n_experts:
            return None
        return self.first_expert, self.n_experts_held

    def num_params(self) -> int:
        c = self
        mla = mla_num_params(c) + 2 * c.d_model
        dense = mla + 3 * c.d_model * c.d_ff
        expert = (mla + c.d_model * c.n_experts + c.n_experts
                  + 3 * c.d_model * c.d_ff_expert
                  * (c.n_experts_held + c.n_shared_experts))
        mtp = c.mtp_depth * (expert + 2 * c.d_model * c.d_model
                             + 3 * c.d_model)
        return (2 * c.vocab_size * c.d_model + c.d_model
                + c.n_dense_layers * dense
                + (c.n_layers - c.n_dense_layers) * expert + mtp)


def mla_num_params(c) -> int:
    """The mixer's parameters (no layer norm) under config `c`."""
    d_qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    q = (c.d_model * c.q_lora_rank + c.q_lora_rank
         + c.q_lora_rank * c.n_heads * d_qk) if c.q_lora_rank \
        else c.d_model * c.n_heads * d_qk
    return (q + c.d_model * (c.kv_lora_rank + c.qk_rope_head_dim)
            + c.kv_lora_rank + c.kv_lora_rank * c.n_heads
            * (c.qk_nope_head_dim + c.v_head_dim)
            + c.n_heads * c.v_head_dim * c.d_model
            + (2 * d_qk if c.qk_head_norm else 0)
            + (c.d_model * c.n_heads if c.attn_gate else 0))


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def _mla_axes(L, config):
    q = {"wq_a": L + ("embed", None), "q_norm": L + (None,),
         "wq_b": L + (None, "heads", "kv")} if config.q_lora_rank \
        else {"wq": L + ("embed", "heads", "kv")}
    if config.qk_head_norm:
        q.update(q_head_norm=L + (None,), k_head_norm=L + (None,))
    if config.attn_gate:
        q["w_attn_gate"] = L + ("embed", "heads")
    return {
        "attn_norm": L + (None,), **q,
        "wkv_a": L + ("embed", None), "kv_norm": L + (None,),
        "wkv_b": L + (None, "heads", "kv"),
        "wo": L + ("heads", "kv", "embed"),
        "mlp_norm": L + (None,),
    }


def _routed_axes(L):
    """The router, the held experts and the shared expert of a layer."""
    ffn = {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
           "w_down": ("mlp", "embed")}
    # the held experts' dim is NOT the `ep` axis's: a share has no exchange
    return {
        "router": L + ("embed", None), "router_bias": L + (None,),
        "experts": {k: L + (None,) + v for k, v in ffn.items()},
        "shared": {k: L + v for k, v in ffn.items()},
    }


def _expert_layer_axes(L, config):
    return {**_mla_axes(L, config), **_routed_axes(L)}


def param_logical_axes(config: MlaMoeConfig) -> Dict[str, Any]:
    L = ("layers",)
    axes = {
        "embed": ("vocab", "embed"),
        "dense": {**_mla_axes(L, config), "w_gate": L + ("embed", "mlp"),
                  "w_up": L + ("embed", "mlp"), "w_down": L + ("mlp", "embed")},
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


def _dense(config, key, shape, fan_in):
    return (jax.random.normal(key, shape, dtype=jnp.float32)
            * (fan_in ** -0.5)).astype(config.dtype)


def _init_ffn(config, keys, lead, width):
    c = config
    return {"w_gate": _dense(c, keys[0], lead + (c.d_model, width), c.d_model),
            "w_up": _dense(c, keys[1], lead + (c.d_model, width), c.d_model),
            "w_down": _dense(c, keys[2], lead + (width, c.d_model), width)}


def _init_mla(config, key):
    """One layer's mixer and its two layer norms."""
    c = config
    d_qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    ones = partial(jnp.ones, dtype=c.dtype)
    ks = jax.random.split(key, 5)
    if c.q_lora_rank:
        q = {"wq_a": _dense(c, ks[0], (c.d_model, c.q_lora_rank), c.d_model),
             "q_norm": ones((c.q_lora_rank,)),
             "wq_b": _dense(c, ks[1], (c.q_lora_rank, c.n_heads, d_qk),
                            c.q_lora_rank)}
    else:
        q = {"wq": _dense(c, ks[0], (c.d_model, c.n_heads, d_qk), c.d_model)}
    if c.qk_head_norm:
        q.update(q_head_norm=ones((d_qk,)), k_head_norm=ones((d_qk,)))
    if c.attn_gate:
        q["w_attn_gate"] = _dense(c, jax.random.fold_in(key, 5),
                                  (c.d_model, c.n_heads), c.d_model)
    return {
        "attn_norm": ones((c.d_model,)), **q,
        "wkv_a": _dense(c, ks[2], (c.d_model, c.kv_lora_rank
                                   + c.qk_rope_head_dim), c.d_model),
        "kv_norm": ones((c.kv_lora_rank,)),
        "wkv_b": _dense(c, ks[3], (c.kv_lora_rank, c.n_heads,
                                   c.qk_nope_head_dim + c.v_head_dim),
                        c.kv_lora_rank),
        "wo": _dense(c, ks[4], (c.n_heads, c.v_head_dim, c.d_model),
                     c.n_heads * c.v_head_dim),
        "mlp_norm": ones((c.d_model,)),
    }


def _init_routed(config, k_r, k_b, ks):
    """A layer's router, its bias, the held experts and the shared one."""
    c = config
    return {
        "router": (jax.random.normal(k_r, (c.d_model, c.n_experts))
                   * 0.02).astype(c.dtype),
        "router_bias": jax.random.normal(k_b, (c.n_experts,)) * 0.01,
        "experts": _init_ffn(c, ks[:3], (c.n_experts_held,), c.d_ff_expert),
        "shared": _init_ffn(c, ks[3:], (),
                            c.n_shared_experts * c.d_ff_expert),
    }


def init(config: MlaMoeConfig, key) -> Dict[str, Any]:
    """Fan-in scaled normal weights in `config.dtype`, norm scales 1, the
    router 0.02 normal, the router's bias float32 N(0, 0.01^2): not zero, so
    that it changes choices wherever two scores lie that close.

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
    dense = partial(_dense, c)

    def dense_layer(key):
        k_attn, *ks = jax.random.split(key, 4)
        return {**_init_mla(c, k_attn), **_init_ffn(c, ks, (), c.d_ff)}

    def expert_layer(key):
        k_attn, k_r, k_b, *ks = jax.random.split(key, 9)
        return {**_init_mla(c, k_attn), **_init_routed(c, k_r, k_b, ks)}

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
# blocks
# --------------------------------------------------------------------------

def _interleaved(w, config: MlaMoeConfig):
    """With `rope_interleave` channel 2i turns with 2i + 1: the even
    channels of the last dim are brought in front of the odd ones, so that
    `llama._rope` turns (i, i + R/2). A permutation of a linear map's
    output channels, so it is applied to the weights that make the rotary
    parts (6 MiB) and not to their [B, S, H, R] outputs. The rotary parts
    stay in that order; q and k get the same treatment, so their products
    are the interleaved form's."""
    if not config.rope_interleave:
        return w
    return jnp.concatenate([w[..., 0::2], w[..., 1::2]], axis=-1)


def _attention(q, k, v, q_rope, k_rope, mesh):
    kw = dict(causal=True, q_rope=q_rope, k_rope=k_rope)
    if mesh is not None and any(
            mesh.shape.get(a, 1) > 1 for a in ("dp", "fsdp", "tp")):
        from ray_tpu.ops.flash_attention import flash_attention_sharded

        return flash_attention_sharded(q, k, v, mesh, **kw)
    return flash_attention(q, k, v, **kw)


def _mla_sublayer(x, p, positions, config: MlaMoeConfig, mesh=None,
                  rules: Optional[LogicalAxisRules] = None):
    """x [B, S, D] -> x + MLA(RMSNorm(x)).

    The flash call takes its operands in the parts the projections make:
    `wq_b` [r, H, 128 + 64] and `wkv_b` [r, H, 128 + 128] stay the published
    parameters and are used by slices of the WEIGHT, so q, the rotary q, k
    and v are each a dot's own output: no [B, S, H, 192] q or k is built,
    nothing is cut out of a [B, S, H, 256] k|v, the rotary key is not
    copied to H heads (`flash_attention` in parts; it sums that key's
    gradient over heads itself) and the interleave is a permutation of
    weight columns (`_interleaved`). Under remat "dots" the saved residuals
    are then the call's operands themselves (the rotary q before RoPE),
    and `_checkpointed` saves its result beside them."""
    c = config
    n_nope, n_lat = c.qk_nope_head_dim, c.kv_lora_rank
    h = _rms_norm(x, p["attn_norm"], c.norm_eps)
    with jax.named_scope("mla.latents"):
        up = partial(jnp.einsum, "bsr,rhk->bshk")
        if c.q_lora_rank:
            c_q = _rms_norm(h @ p["wq_a"], p["q_norm"], c.norm_eps)
            w_q = p["wq_b"]
        else:  # no q latent: the projection straight from the layer input
            c_q, w_q = h, p["wq"]

        def head_norm(x, name, rotary):
            # the per-head norm, a part at a time (so the ONE rotary key
            # stays every head's); the rotary channels' scales in the
            # order `_interleaved` leaves the channels in
            if not c.qk_head_norm:
                return x
            scale = _interleaved(p[name][n_nope:], c) if rotary \
                else p[name][:n_nope]
            return _rms_norm(x, scale, c.norm_eps)

        turned = lambda x, name: _rope(  # noqa: E731
            head_norm(x, name, True), positions, c.rope_theta)
        q = head_norm(up(c_q, w_q[..., :n_nope]), "q_head_norm", False)
        q_rope = turned(up(c_q, _interleaved(w_q[..., n_nope:], c)),
                        "q_head_norm")
        kv_a = h @ jnp.concatenate(
            [p["wkv_a"][:, :n_lat], _interleaved(p["wkv_a"][:, n_lat:], c)],
            axis=-1)
        c_kv = _rms_norm(kv_a[..., :n_lat], p["kv_norm"], c.norm_eps)
        k = head_norm(up(c_kv, p["wkv_b"][..., :n_nope]), "k_head_norm",
                      False)
        v = up(c_kv, p["wkv_b"][..., n_nope:])
        # one rotary key, the same for every head
        k_rope = turned(kv_a[..., None, n_lat:], "k_head_norm")
    with jax.named_scope("mla.attend"):
        # scores over n_nope + n_rope channels, scaled by their root
        attn = _attention(q, k, v, q_rope, k_rope, mesh)
    if c.attn_gate:
        with jax.named_scope("mla.gate"):
            attn = llama._head_gated(attn, h, p["w_attn_gate"])
    device_profiler.count("mla.layers", 1)  # per lowering
    device_profiler.count("mla.attend_parts", 1)
    x = x + jnp.einsum("bshk,hkd->bsd", attn, p["wo"])
    return _residual(x, mesh, rules)


def _routing(h, p, config):
    """h [T, D], what this model's router reads -> `route`'s choice at the
    config's `score`; a layer without a `router_bias` chooses on the scores
    alone."""
    c = config
    return route(h, p["router"], c.experts_per_token, c.norm_topk_prob,
                 score=c.score, bias=p.get("router_bias"),
                 scale=c.routed_scaling_factor, n_group=c.n_group,
                 topk_group=c.topk_group)


def _expert_sublayer(x, p, config: MlaMoeConfig, mesh=None,
                     rules: Optional[LogicalAxisRules] = None, routing=None,
                     form: str = "swiglu"):
    """x [B, S, D] -> (x + routed + shared experts of RMSNorm(x), the
    chosen experts [B * S, k]). The choice is `_routing` of that normed
    input, or `routing`, the same formed EARLIER from what the model's
    router reads instead (`models/window_moe.py`: the attention's input);
    the experts are of `form` (`moe_layer`), beside a shared SwiGLU every
    token passes where the layer has one (`p["shared"]`)."""
    c = config
    if mesh is not None and mesh.shape.get("ep", 1) > 1:
        raise NotImplementedError(
            "mla_moe runs its experts in one program (all of them, or one "
            "chip's share without the exchange): no `ep` mesh axis")
    b, s, d = x.shape
    h = _rms_norm(x, p["mlp_norm"], c.norm_eps)
    rows = h.reshape(b * s, d)
    if routing is None:
        routing = _routing(rows, p, c)
    routed, aux = moe_layer(rows, None, p["experts"], c.experts_per_token,
                            held=c.held, form=form, routing=routing)
    if "shared" not in p:
        return _residual(x + routed.reshape(b, s, d), mesh, rules), \
            aux.experts
    with jax.named_scope("moe.shared"):
        sh = p["shared"]
        shared = (jax.nn.silu(h @ sh["w_gate"]) * (h @ sh["w_up"])) \
            @ sh["w_down"]
    x = x + routed.reshape(b, s, d) + shared
    return _residual(x, mesh, rules), aux.experts


def _expert_layer(x, p, positions, config, mesh, rules):
    x = _mla_sublayer(x, p, positions, config, mesh, rules)
    return _expert_sublayer(x, p, config, mesh, rules)


def _dense_layer(x, p, positions, config, mesh, rules):
    x = _mla_sublayer(x, p, positions, config, mesh, rules)
    return llama._mlp_sublayer(x, p, config, mesh, rules)


def _checkpointed(fn, config, names=RESIDUAL_NAMES):
    """`fn` under `config.remat_policy` (llama's names) and, whatever that
    saves, the kernels' own residuals `names` beside it (the flash call's
    output and lse, named by `ops/flash_attention.py`: 65 MiB a layer at
    B 4 x S 2048), so the backward pass runs no second forward kernel.
    "full" saves nothing."""
    if not config.remat:
        return fn
    policy = _remat_policy(config)
    if policy is not None:
        policy = jax.checkpoint_policies.save_from_both_policies(
            policy, jax.checkpoint_policies.save_only_these_names(*names))
    return jax.checkpoint(fn, policy=policy)


def forward_hidden(params, tokens, config: MlaMoeConfig, mesh=None,
                   rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> (final-norm hidden states [B, S, D], the chosen
    experts of every expert layer [L, B * S, k])."""
    c = config
    lc = partial(with_logical_constraint, mesh=mesh, rules=rules)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    table = lc(params["embed"], ("vocab", "act_embed"))
    x = llama.embed_rows(table, tokens, mesh).astype(c.dtype)
    x = _residual(x, mesh, rules)
    kw = dict(positions=positions, config=c, mesh=mesh, rules=rules)
    dense = _checkpointed(partial(_dense_layer, **kw), c)
    x, _ = jax.lax.scan(lambda x, p: (dense(x, p), None), x, params["dense"])
    x, chosen = jax.lax.scan(_checkpointed(partial(_expert_layer, **kw), c),
                             x, params["layers"])
    return _rms_norm(x, params["final_norm"], c.norm_eps), chosen


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
        emb = llama.embed_rows(params["embed"], next_tokens,
                               mesh).astype(c.dtype)
        x = jnp.concatenate([_rms_norm(emb, p["enorm"], c.norm_eps),
                             _rms_norm(hidden, p["hnorm"], c.norm_eps)],
                            axis=-1) @ p["eh_proj"]
        block = _checkpointed(partial(
            _expert_layer, positions=positions, config=c, mesh=mesh,
            rules=rules), c)
        x, chosen = block(_residual(x, mesh, rules),
                          jax.tree.map(lambda a: a[0], p["block"]))
        device_profiler.count("mtp.depth", 1)  # per lowering
        return _rms_norm(x, p["final_norm"], c.norm_eps), chosen


def forward(params, tokens, config: MlaMoeConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> next-token logits [B, S, V] float32."""
    x, _ = forward_hidden(params, tokens, config, mesh, rules)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"]).astype(jnp.float32)


def _split(batch):
    if "inputs" in batch:
        return batch["inputs"], batch["targets"], batch.get("mask")
    tokens = batch["tokens"]
    return tokens[:, :-1], tokens[:, 1:], None


def mtp_targets(targets, mask=None):
    """targets [B, S] (t_{i+1} at position i) -> (t_{i+2} [B, S], mask
    [B, S] float32): the targets one further on; the last position has none
    and is masked (its id is 0, never read)."""
    b, s = targets.shape
    shifted = jnp.concatenate(
        [targets[:, 1:], jnp.zeros((b, 1), targets.dtype)], axis=1)
    last = (jnp.arange(s) < s - 1).astype(jnp.float32)[None]
    return shifted, last * (jnp.ones((b, s)) if mask is None else mask)


def loss_fn(params, batch, config: MlaMoeConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """Next-token CE + `mtp_loss_coef` * the MTP block's CE of the token
    after (both through `llama.chunked_ce`, masked by batch["mask"] when
    given). Scalar return (make_train_step contract)."""
    c = config
    inputs, targets, mask = _split(batch)
    chunk = c.loss_chunk_size or inputs.shape[1]
    hidden, _ = forward_hidden(params, inputs, c, mesh, rules)
    loss = llama.chunked_ce(hidden, params["lm_head"], targets, mask,
                            chunk=chunk)
    if c.mtp_depth:
        h_mtp, _ = mtp_hidden(params, hidden, targets, c, mesh, rules)
        loss = loss + c.mtp_loss_coef * llama.chunked_ce(
            h_mtp, params["lm_head"], *mtp_targets(targets, mask),
            chunk=chunk)
    return loss


@partial(jax.jit, static_argnames=("config",))
def routing_stats(params, tokens, config: MlaMoeConfig):
    """tokens [B, S + 1] (as `loss_fn`'s {"tokens": ...}) -> int32
    [expert layers + mtp_depth]: the LIVE rows of each expert layer (the MTP
    block's last), the (token, choice) pairs whose expert is held here.
    Outside the train step, for tests and chip runs."""
    c = config
    inputs, targets, _ = _split({"tokens": tokens})
    hidden, chosen = forward_hidden(params, inputs, c)
    if c.mtp_depth:
        chosen = jnp.concatenate(
            [chosen, mtp_hidden(params, hidden, targets, c)[1][None]])
    local = chosen - c.first_expert
    return jnp.sum((local >= 0) & (local < c.n_experts_held), axis=(1, 2),
                   dtype=jnp.int32)


def routing_loads(params, tokens, config: MlaMoeConfig):
    """-> float32, as `routing_stats`: each block's live rows over the rows
    of the capacity it runs at (`moe.capacity_load`): what of its buffer
    the row moves visit."""
    c = config
    rows = tokens.shape[0] * (tokens.shape[1] - 1)
    return moe.capacity_load(
        routing_stats(params, tokens, c), moe.share_capacities(
            rows, c.experts_per_token, c.n_experts_held, c.n_experts))
