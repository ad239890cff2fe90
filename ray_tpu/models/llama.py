"""Llama-family decoder-only transformer, TPU-first: a model module like
the others, over the layer library `models/blocks.py`.

GQA attention (Pallas flash kernels on TPU, ring attention when the mesh has
an `sp` axis), RMSNorm, SwiGLU, RoPE (`blocks.attn_sublayer`,
`blocks.mlp_sublayer`), scan-over-layers with per-layer remat
(`jax.checkpoint`) so compile time and HBM stay flat as depth grows, and
logical sharding annotations (batch/embed/heads/mlp/vocab) that lower to
DP/FSDP/TP on any mesh via ray_tpu.parallel.sharding. Here too, and only
here, the serving path: the paged KV pool and the incremental forward over
it (`init_paged_kv_cache`, `forward_with_paged_cache`), which the serving
configuration names through this module.

Capability note: the reference has no model zoo of its own — its Train/Serve
stacks wrap external Torch models. Here the model layer is in-framework so
parallelism is native (SURVEY.md §5, §7).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import blocks
from ray_tpu.models.blocks import (
    attn_sublayer, mlp_sublayer, qkv, remat_policy, rms_norm)
from ray_tpu.parallel.sharding import LogicalAxisRules, with_logical_constraint


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32_000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_head: int = 128
    d_ff: int = 14_336
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    remat: bool = True
    # "full" recomputes everything; "dots" saves matmul outputs and
    # recomputes only cheap elementwise ops.
    remat_policy: str = "dots"
    # >0: compute the training CE over sequence chunks of this size so the
    # full [B,S,V] fp32 logits tensor never materializes (chunked_ce).
    loss_chunk_size: int = 0
    use_ring_attention: bool = False  # set when mesh sp-axis > 1
    # RMSNorm with a learned scale over ALL channels of the q projection and
    # of the k projection, before the split into heads and RoPE (OLMoE).
    qk_norm: bool = False

    @staticmethod
    def tiny(vocab_size: int = 512) -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=vocab_size, d_model=128, n_layers=2, n_heads=4,
            n_kv_heads=2, d_head=32, d_ff=256, max_seq_len=512,
        )

    @staticmethod
    def small_1b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=32_000, d_model=2048, n_layers=16, n_heads=16,
            n_kv_heads=8, d_head=128, d_ff=5632,
        )

    def num_params(self) -> int:
        per_layer = (
            self.d_model * self.n_heads * self.d_head      # wq
            + 2 * self.d_model * self.n_kv_heads * self.d_head  # wk, wv
            + self.n_heads * self.d_head * self.d_model    # wo
            + 3 * self.d_model * self.d_ff                 # gate, up, down
            + 2 * self.d_model                             # norms
            + self.qk_norm * (self.n_heads + self.n_kv_heads) * self.d_head
        )
        return (
            self.vocab_size * self.d_model                 # embed
            + self.n_layers * per_layer
            + self.d_model                                 # final norm
            + self.d_model * self.vocab_size               # lm head
        )


def param_logical_axes(config: LlamaConfig) -> Dict[str, Any]:
    """Logical axis names per parameter (layers stacked on 'layers')."""
    L = ("layers",)
    qk_norm = {"q_norm": L + ("heads", "kv"), "k_norm": L + ("heads", "kv")} \
        if config.qk_norm else {}
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            **qk_norm, **blocks.attn_axes(L),
            "mlp_norm": L + (None,), **blocks.ffn_axes(L),
        },
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }


def init(config: LlamaConfig, key) -> Dict[str, Any]:
    c = config
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    dense = partial(blocks.dense, c)

    def layer_params(key):
        ks = jax.random.split(key, 7)
        qk_norm = {
            "q_norm": jnp.ones((c.n_heads, c.d_head), dtype=c.dtype),
            "k_norm": jnp.ones((c.n_kv_heads, c.d_head), dtype=c.dtype),
        } if c.qk_norm else {}
        return {
            **qk_norm,
            "attn_norm": jnp.ones((c.d_model,), dtype=c.dtype),
            "wq": dense(ks[0], (c.d_model, c.n_heads, c.d_head), c.d_model),
            "wk": dense(ks[1], (c.d_model, c.n_kv_heads, c.d_head), c.d_model),
            "wv": dense(ks[2], (c.d_model, c.n_kv_heads, c.d_head), c.d_model),
            "wo": dense(ks[3], (c.n_heads, c.d_head, c.d_model),
                        c.n_heads * c.d_head),
            "mlp_norm": jnp.ones((c.d_model,), dtype=c.dtype),
            "w_gate": dense(ks[4], (c.d_model, c.d_ff), c.d_model),
            "w_up": dense(ks[5], (c.d_model, c.d_ff), c.d_model),
            "w_down": dense(ks[6], (c.d_ff, c.d_model), c.d_ff),
        }

    layer_keys = jax.random.split(k_layers, c.n_layers)
    layers = jax.vmap(layer_params)(layer_keys)
    return {
        "embed": dense(k_embed, (c.vocab_size, c.d_model), c.d_model),
        "layers": layers,
        "final_norm": jnp.ones((c.d_model,), dtype=c.dtype),
        "lm_head": dense(k_head, (c.d_model, c.vocab_size), c.d_model),
    }


def _layer(x, params, positions, config: LlamaConfig, mesh=None,
           rules: Optional[LogicalAxisRules] = None):
    x = attn_sublayer(x, params, positions, config, mesh, rules)
    return mlp_sublayer(x, params, config, mesh, rules)


def forward_hidden(params, tokens, config: LlamaConfig, mesh=None,
                   rules: Optional[LogicalAxisRules] = None):
    """tokens [B,S] -> final-norm hidden states [B,S,D] (pre-lm_head)."""
    c = config
    x, positions = blocks.embed_tokens(params, tokens, mesh, rules)
    x = blocks.residual(x.astype(c.dtype), mesh, rules)

    layer_fn = partial(_layer, positions=positions, config=c, mesh=mesh,
                       rules=rules)
    if c.remat:
        layer_fn = jax.checkpoint(layer_fn, policy=remat_policy(c))

    def scan_body(x, layer_p):
        return layer_fn(x, layer_p), None

    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    return rms_norm(x, params["final_norm"], c.norm_eps)


def forward(params, tokens, config: LlamaConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """tokens: [B, S] int32 -> logits [B, S, vocab] (cast to fp32)."""
    lc = partial(with_logical_constraint, mesh=mesh, rules=rules)
    x = forward_hidden(params, tokens, config, mesh, rules)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    logits = lc(logits, ("batch", "seq", "act_vocab"))
    return logits.astype(jnp.float32)


def _cached_attention(q, k_cache, v_cache, lengths, config: LlamaConfig):
    """q: [B,S,H,K] new queries at positions lengths..lengths+S;
    k/v_cache: [B,T,kv,K] full cache (already containing the new keys).
    Masks out cache positions >= lengths+S and enforces causality within
    the new block.

    Decode is HBM-bound on the cache read, so the einsums are grouped-query
    aware: q is reshaped to [B,S,kv,rep,K] and contracted against the bf16
    cache directly (fp32 accumulation via preferred_element_type) — no
    jnp.repeat head broadcast, no materialized fp32 cache copy."""
    c = config
    b, s, h, d = q.shape
    t = k_cache.shape[1]
    rep = c.n_heads // c.n_kv_heads
    qg = q.reshape(b, s, c.n_kv_heads, rep, d)
    scores = jnp.einsum(
        "bsgrk,btgk->bgrst", qg, k_cache,
        preferred_element_type=jnp.float32) / (d ** 0.5)
    # position j is visible to query i (absolute pos lengths+i) iff j <= pos.
    q_pos = (lengths[:, None, None, None, None]
             + jnp.arange(s)[None, None, None, :, None])
    j_pos = jnp.arange(t)[None, None, None, None, :]
    scores = jnp.where(j_pos <= q_pos, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrst,btgk->bsgrk", probs.astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, d).astype(q.dtype)


def _decode_attention(q, k_new, v_new, k_cache, v_cache, lengths,
                      config: LlamaConfig):
    """Single-token attention where the current token's K/V is NOT yet in
    the cache: q/k_new/v_new [B,1,H|kv,K], k/v_cache [B,T,kv,K] holding
    positions 0..lengths-1. The self-attention term is computed directly
    from k_new/v_new so the (donated) cache only needs ONE top-level
    scatter per decode step instead of a per-layer read+rewrite."""
    c = config
    b, s, h, d = q.shape
    t = k_cache.shape[1]
    rep = c.n_heads // c.n_kv_heads
    qg = q.reshape(b, s, c.n_kv_heads, rep, d)
    scores = jnp.einsum(
        "bsgrk,btgk->bgrst", qg, k_cache,
        preferred_element_type=jnp.float32) / (d ** 0.5)
    j_pos = jnp.arange(t)[None, None, None, None, :]
    valid = j_pos < lengths[:, None, None, None, None]
    scores = jnp.where(valid, scores, -1e30)
    self_score = jnp.einsum(
        "bsgrk,bgk->bgrs", qg, k_new[:, 0],
        preferred_element_type=jnp.float32) / (d ** 0.5)
    all_scores = jnp.concatenate([scores, self_score[..., None]], axis=-1)
    probs = jax.nn.softmax(all_scores, axis=-1)
    out = jnp.einsum("bgrst,btgk->bsgrk", probs[..., :t].astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    out = out + jnp.einsum("bgrs,bgk->bsgrk",
                           probs[..., t].astype(jnp.float32),
                           v_new[:, 0].astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)


def init_paged_kv_cache(config: LlamaConfig, n_blocks: int,
                        block_size: int, dtype=None) -> Dict[str, Any]:
    """Block-pool KV cache (PagedAttention layout, TPU-shaped): arrays
    [n_layers, n_blocks, block_size, n_kv_heads, d_head]. Sequences map
    logical positions onto pool blocks through a block table, so HBM is
    budgeted by TOTAL tokens in flight instead of batch x max_seq_len
    (ragged/long sequences stop reserving worst-case rows). Block 0 is
    reserved as a scratch target for masked writes."""
    c = config
    dtype = dtype or c.dtype
    shape = (c.n_layers, n_blocks, block_size, c.n_kv_heads, c.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _attn_sublayer_paged(x, params, positions, config: LlamaConfig,
                         k_pool, v_pool, block_table, lengths, valid):
    """Attention over a paged KV pool for ONE layer.

    k_pool/v_pool: [n_blocks, bs, kv, d]; block_table: [B, max_blocks];
    positions: [B, S] logical positions of the new tokens; valid: [B, S]
    bool (False rows scatter into the reserved scratch block 0).
    The per-layer gather materializes [B, max_blocks*bs, kv, d]
    transiently, and logical position t lands at gathered index t, so
    _cached_attention's length masking applies as over a dense cache."""
    c = config
    q, k, v = qkv(x, params, positions, c)
    n_blocks, bs, kvh, d = k_pool.shape
    b, s = positions.shape
    blk = jnp.take_along_axis(block_table, positions // bs, axis=1)
    flat = jnp.where(valid, blk * bs + positions % bs, 0)  # 0 = scratch

    def write_then_gather(pool, new):
        rows = pool.reshape(n_blocks * bs, kvh, d).at[flat.reshape(-1)].set(
            new.reshape(b * s, kvh, d).astype(pool.dtype))
        pool = rows.reshape(pool.shape)
        return pool, jnp.take(pool, block_table, axis=0).reshape(
            b, -1, kvh, d)

    k_pool, k_all = write_then_gather(k_pool, k)
    v_pool, v_all = write_then_gather(v_pool, v)
    attn = _cached_attention(q, k_all, v_all, lengths, c)
    x = x + jnp.einsum("bshk,hkd->bsd", attn, params["wo"])
    return x, (k_pool, v_pool)


def _attn_sublayer_paged_decode(x, params, positions, config: LlamaConfig,
                                k_pool, v_pool, block_table):
    """Decode-step (S=1) paged attention: gathers each row's KV from the
    pool (positions < lengths only — the pool is READ-ONLY here), adds
    the new token's self-attention term directly, and returns the new
    K/V for a single deferred top-level pool scatter (see
    forward_with_paged_cache)."""
    c = config
    q, k, v = qkv(x, params, positions, c)
    n_blocks, bs, kvh, d = k_pool.shape
    b = positions.shape[0]
    # gathered index t == logical position t, so length masking applies
    k_all = jnp.take(k_pool, block_table, axis=0).reshape(b, -1, kvh, d)
    v_all = jnp.take(v_pool, block_table, axis=0).reshape(b, -1, kvh, d)
    k, v = k.astype(k_pool.dtype), v.astype(v_pool.dtype)
    attn = _decode_attention(q, k, v, k_all, v_all, positions[:, 0], c)
    x = x + jnp.einsum("bshk,hkd->bsd", attn, params["wo"])
    return x, (k, v)


def forward_with_paged_cache(params, tokens, pool, block_table, lengths,
                             config: LlamaConfig, valid=None):
    """Incremental forward for generation over a paged pool (see
    init_paged_kv_cache): prefill when S > 1, decode at S = 1.

    tokens: [B, S] the NEW tokens, logically at positions
    lengths..lengths+S; lengths: [B] int32, tokens already in the pool per
    row; valid: optional [B, S] bool for padded prefill tails (invalid
    positions write to the scratch block and are masked from attention by
    `lengths`).
    -> (logits [B, S, vocab] fp32, new_pool)"""
    c = config
    b, s = tokens.shape
    positions = lengths[:, None] + jnp.arange(s)[None, :]
    if valid is None:
        valid = jnp.ones((b, s), bool)
    # Same embed-dim constraint as `blocks.embed_tokens`: under an ambient
    # sharded mesh a gather from an fsdp-sharded table forces a full-remat
    # reshard.
    table = with_logical_constraint(params["embed"], ("vocab", "act_embed"))
    x = blocks.embed_rows(table, tokens).astype(c.dtype)

    # Decode fast path (S = 1): layers only READ the pool; the new K/V comes
    # out as [L,B,1,kv,K] ys and lands in the (donated) pool with one
    # in-place scatter instead of a per-layer full-pool rewrite. Prefill
    # layers write their rows and hand the layer's pool back as ys.
    attn = _attn_sublayer_paged_decode if s == 1 else partial(
        _attn_sublayer_paged, lengths=lengths, valid=valid)

    def scan_body(x, layer_in):
        layer_p, kp, vp = layer_in
        x, kv = attn(x, layer_p, positions, c, kp, vp, block_table)
        return mlp_sublayer(x, layer_p, c), kv

    x, (k_out, v_out) = jax.lax.scan(
        scan_body, x, (params["layers"], pool["k"], pool["v"]))
    new_pool = {"k": k_out, "v": v_out}
    if s == 1:
        n_blocks, bs = pool["k"].shape[1], pool["k"].shape[2]
        # the one new row a sequence, addressed as _attn_sublayer_paged does
        blk = jnp.take_along_axis(block_table, positions // bs, axis=1)
        flat = jnp.where(valid, blk * bs + positions % bs, 0)[:, 0]

        def place(old, new_rows):
            rows = old.reshape(old.shape[0], n_blocks * bs, *old.shape[3:])
            return rows.at[:, flat].set(new_rows[:, :, 0]).reshape(old.shape)

        new_pool = {name: place(pool[name], new_rows)
                    for name, new_rows in new_pool.items()}
    x = rms_norm(x, params["final_norm"], c.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits.astype(jnp.float32), new_pool


def loss_fn(params, batch, config: LlamaConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """Next-token cross-entropy. batch: {"tokens": [B, S]} (targets are the
    shifted tokens) or explicit {"inputs", "targets", "mask"}.
    With config.loss_chunk_size > 0 the CE is computed chunk-by-chunk over
    the sequence (`blocks.chunked_ce`) so full-vocab logits never
    materialize, and a chunk's logits are formed once: its gradient in the
    same pass."""
    inputs, targets, mask = blocks.split_batch(batch)
    if config.loss_chunk_size:
        hidden = forward_hidden(params, inputs, config, mesh, rules)
        return blocks.chunked_ce(hidden, params["lm_head"], targets, mask,
                                 chunk=config.loss_chunk_size)
    logits = forward(params, inputs, config, mesh, rules)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        nll = nll * mask
        denom = jnp.maximum(jnp.sum(mask), 1.0)
    else:
        denom = nll.size
    return jnp.sum(nll) / denom
