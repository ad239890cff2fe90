"""A tokenizer-free byte decoder with EVA attention and several next-byte
heads (EvaByte by config: `model_type` `evabyte`, `attention_class` `eva`),
TPU-first, training only.

As the released modelling code computes it (bf16 weights, `norm_eps` 1e-5,
s = d_head ** -0.5):

    x_0 = E[bytes]                     x kept in FLOAT32 (`fp32_skip_add`)
    layer:  x = x + W_o EVA(norm(x))
            x = x + W_down (silu(W_gate h) * W_up h),  h = norm(x)
    norm(x) = x / rms(x) * (1 + g)     `norm_add_unit_offset`; statistics in
                                       float32, the output in bf16
    q, k, v = heads of W_q h, W_k h, W_v h   no bias, as many KV heads as
              query heads; RoPE (all channels) on q and k BEFORE the pooling
    EVA: `ops/eva.py`: a query sees, in ONE softmax, its `window`'s own
         bytes up to itself and the `chunk`-byte summaries (pooled under a
         learned `phi`, plus a learned `mu`, a head) of every earlier window
    logits = W_head norm(x_L)          [D, pred_heads x V], float32, untied
    loss = mean over heads i of the mean, over the positions t that have
           one, of CE(logits[t, i], targets[t + i])     (targets[t] is the
           byte after t: head i predicts the byte 1 + i on)

The residual stream is float32 and every sublayer's input bf16: the norm
rounds once, the matmuls run in bf16 with float32 accumulation, and a
branch's output is added in float32. Over the layer library
(`models/blocks.py`): the SwiGLU is `blocks.swiglu` and the rotary embedding
`blocks.rope`; the heads' loss is ONE `blocks.chunked_ce` call over the whole
head in `pred_heads` groups. Layers are alike and scanned; remat is per
layer (`blocks.checkpointed`): under "residuals" a
layer keeps its float32 input and the flash call's o and lse and recomputes
the rest, the MLP in blocks of `_MLP_ROWS` rows, each recomputed and
differentiated on its own, so that gate, up and their product exist a block
at a time ([8192, 11008] for [32768, 11008] at the published widths: the v5e
compiler places four layers at 14.84 of 15.75 GiB so, at 15.15 whole).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import blocks
from ray_tpu.models.blocks import residual
from ray_tpu.ops import eva
from ray_tpu.ops.flash_attention import RESIDUAL_NAMES as FLASH_RESIDUALS
from ray_tpu.parallel.sharding import LogicalAxisRules, with_logical_constraint


@dataclasses.dataclass(frozen=True)
class EvaByteConfig:
    vocab_size: int = 320
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    d_head: int = 128
    d_ff: int = 11_008
    window: int = 2048
    chunk: int = 16
    pred_heads: int = 8
    rope_theta: float = 100_000.0
    norm_eps: float = 1e-5
    init_std: float = 0.01275
    dtype: Any = jnp.bfloat16
    remat: bool = True
    remat_policy: str = "residuals"
    loss_chunk_size: int = 0

    def __post_init__(self):
        if self.window % self.chunk:
            raise ValueError(f"a window of {self.window} bytes is not whole "
                             f"chunks of {self.chunk}")

    @staticmethod
    def tiny(vocab_size: int = 320, **over) -> "EvaByteConfig":
        return EvaByteConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, n_layers=3, n_heads=4,
            d_head=16, d_ff=96, window=32, chunk=4, pred_heads=3), **over})

    def num_params(self) -> int:
        d = self.d_model
        return (self.vocab_size * d + self.n_layers * layer_num_params(self)
                + d + d * self.pred_heads * self.vocab_size)


# rows of the MLP recomputed and differentiated at a time, where they divide
# a longer sequence (above)
_MLP_ROWS = 8192


def layer_num_params(c) -> int:
    """One layer's parameters: q, k, v, o; gate, up, down; two norms; the
    pooling vector and the offset."""
    hk = c.n_heads * c.d_head
    return 4 * c.d_model * hk + 3 * c.d_model * c.d_ff + 2 * c.d_model + 2 * hk


# --------------------------------------------------------------------------
# parameters
# --------------------------------------------------------------------------

def param_logical_axes(config: EvaByteConfig) -> Dict[str, Any]:
    L = ("layers",)
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            **blocks.attn_axes(L), "phi": L + ("heads", "kv"),
            "mu": L + ("heads", "kv"),
            "mlp_norm": L + (None,), **blocks.ffn_axes(L),
        },
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }


def init(config: EvaByteConfig, key) -> Dict[str, Any]:
    """Every matrix and the embedding N(0, `init_std`^2), as published; the
    norms' g 0 (a unit scale); `phi` and `mu` N(0, 1) clamped to [-1, 1],
    times d_head ** -0.5 (the published initialisation: it carries the
    softmax scale the pooling logits are taken without)."""
    c = config

    def normal(key, shape):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * c.init_std).astype(c.dtype)

    def pooling(key):
        return (jnp.clip(jax.random.normal(
            key, (c.n_heads, c.d_head), dtype=jnp.float32), -1.0, 1.0)
            * c.d_head ** -0.5).astype(c.dtype)

    def layer(key):
        ks = jax.random.split(key, 9)
        heads = (c.d_model, c.n_heads, c.d_head)
        return {
            "attn_norm": jnp.zeros((c.d_model,), c.dtype),
            "wq": normal(ks[0], heads), "wk": normal(ks[1], heads),
            "wv": normal(ks[2], heads),
            "wo": normal(ks[3], (c.n_heads, c.d_head, c.d_model)),
            "phi": pooling(ks[4]), "mu": pooling(ks[5]),
            "mlp_norm": jnp.zeros((c.d_model,), c.dtype),
            "w_gate": normal(ks[6], (c.d_model, c.d_ff)),
            "w_up": normal(ks[7], (c.d_model, c.d_ff)),
            "w_down": normal(ks[8], (c.d_ff, c.d_model)),
        }

    k_embed, k_layers, k_head = jax.random.split(key, 3)
    return {
        "embed": normal(k_embed, (c.vocab_size, c.d_model)),
        "layers": jax.vmap(layer)(jax.random.split(k_layers, c.n_layers)),
        "final_norm": jnp.zeros((c.d_model,), c.dtype),
        "lm_head": normal(k_head,
                          (c.d_model, c.pred_heads * c.vocab_size)),
    }


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def _norm(x, g, config):
    """x / rms(x) * (1 + g): statistics and the unit offset in float32, the
    result rounded once to the sublayers' dtype."""
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + config.norm_eps)
            * (1.0 + g.astype(jnp.float32))).astype(config.dtype)


def _eva_sublayer(x, p, positions, config, mesh, rules):
    """x [B, S, D] float32 -> x + W_o EVA(norm(x)); RoPE on q and k before
    the pooling, so a summary pools rotated keys."""
    c = config
    h = _norm(x, p["attn_norm"], c)
    q, k, v = (jnp.einsum("bsd,dhk->bshk", h, p[w])
               for w in ("wq", "wk", "wv"))
    q = blocks.rope(q, positions, c.rope_theta)
    k = blocks.rope(k, positions, c.rope_theta)
    attn = eva.eva_attention(q, k, v, p["phi"], p["mu"], c.window, c.chunk,
                             mesh=mesh)
    out = jnp.einsum("bshk,hkd->bsd", attn, p["wo"])
    return residual(x + out.astype(jnp.float32), mesh, rules)


def _mlp_sublayer(x, p, config, mesh, rules):
    """x + `blocks.swiglu`(norm(x)), in blocks of `_MLP_ROWS` rows where
    they divide a longer sequence."""
    c = config
    lc = partial(with_logical_constraint, mesh=mesh, rules=rules)
    h = _norm(x, p["mlp_norm"], c)
    weights = {w: p[w] for w in ("w_gate", "w_up", "w_down")}
    b, s, d = h.shape
    if s <= _MLP_ROWS or s % _MLP_ROWS:
        out = blocks.swiglu(h, weights, lc)
    else:
        block = jax.checkpoint(lambda rows: blocks.swiglu(rows, weights, lc))
        out = jax.lax.map(block, jnp.moveaxis(
            h.reshape(b, -1, _MLP_ROWS, d), 1, 0))
        out = jnp.moveaxis(out, 0, 1).reshape(b, s, d)
    return residual(x + out.astype(jnp.float32), mesh, rules)


def _layer(x, p, positions, config, mesh, rules):
    x = _eva_sublayer(x, p, positions, config, mesh, rules)
    return _mlp_sublayer(x, p, config, mesh, rules)


def forward_hidden(params, tokens, config: EvaByteConfig, mesh=None,
                   rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> final-norm hidden states [B, S, D] (bf16)."""
    c = config
    x, positions = blocks.embed_tokens(params, tokens, mesh, rules)
    x = residual(x.astype(jnp.float32), mesh, rules)
    layer = blocks.checkpointed(
        partial(_layer, positions=positions, config=c, mesh=mesh,
                rules=rules), c, FLASH_RESIDUALS)
    x, _ = jax.lax.scan(lambda x, p: (layer(x, p), None), x,
                        params["layers"])
    return _norm(x, params["final_norm"], c)


def forward(params, tokens, config: EvaByteConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """tokens [B, S] -> logits [B, S, pred_heads, V] float32: head i at
    position t scores the byte 1 + i on."""
    c = config
    x = forward_hidden(params, tokens, c, mesh, rules)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits.astype(jnp.float32).reshape(
        *tokens.shape, c.pred_heads, c.vocab_size)


def head_targets(targets, mask, heads: int):
    """targets [B, S] (the byte after t at position t), mask [B, S] or None
    -> (targets [B, S, heads], weights [B, S, heads] float32): head i's
    target at t is targets[t + i], with no target (weight 0) where t + i >=
    S; a head's weights sum to 1 / heads over the positions that have one,
    so the weighted sum of the terms is the mean over heads of each head's
    mean."""
    s = targets.shape[1]
    at = jnp.arange(s)[:, None] + jnp.arange(heads)[None, :]  # [S, heads]
    weights = jnp.broadcast_to((at < s).astype(jnp.float32),
                               targets.shape + (heads,))
    if mask is not None:
        weights = weights * mask.astype(jnp.float32)[:, :, None]
    weights = weights / (heads * jnp.maximum(
        jnp.sum(weights, axis=(0, 1)), 1.0))
    return targets[:, jnp.minimum(at, s - 1)], weights


def loss_fn(params, batch, config: EvaByteConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """The `pred_heads` heads' mean CE through ONE `blocks.chunked_ce` over
    the whole head in groups, rows masked by batch["mask"] when given.
    Scalar return (make_train_step contract)."""
    c = config
    inputs, targets, mask = blocks.split_batch(batch)
    hidden = forward_hidden(params, inputs, c, mesh, rules)
    targets, weights = head_targets(targets, mask, c.pred_heads)
    return blocks.chunked_ce(
        hidden, params["lm_head"], targets, weights,
        chunk=c.loss_chunk_size or inputs.shape[1], denominator=1.0,
        groups=c.pred_heads)
