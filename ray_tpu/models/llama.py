"""Llama-family decoder-only transformer, TPU-first.

Flagship model for the framework's training/serving stacks: GQA attention
(Pallas flash kernels on TPU, ring attention when the mesh has an `sp` axis),
RMSNorm, SwiGLU, RoPE, scan-over-layers with per-layer remat
(`jax.checkpoint`) so compile time and HBM stay flat as depth grows, and
logical sharding annotations (batch/embed/heads/mlp/vocab) that lower to
DP/FSDP/TP on any mesh via ray_tpu.parallel.sharding.

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
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name

from ray_tpu.ops.flash_attention import flash_attention
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
    # recomputes only cheap elementwise ops (~6% faster at 500M/1-chip,
    # still fits long-seq activations in HBM).
    remat_policy: str = "dots"
    # >0: compute the training CE over sequence chunks of this size so the
    # full [B,S,V] fp32 logits tensor never materializes (chunked_ce).
    loss_chunk_size: int = 0
    use_ring_attention: bool = False  # set when mesh sp-axis > 1
    # RMSNorm with a learned scale over ALL channels of the q projection and
    # of the k projection, before the split into heads and RoPE (OLMoE).
    qk_norm: bool = False

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig(
            vocab_size=128_256, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, d_head=128, d_ff=14_336,
        )

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
            **qk_norm,
            "attn_norm": L + (None,),
            "wq": L + ("embed", "heads", "kv"),
            "wk": L + ("embed", "heads", "kv"),
            "wv": L + ("embed", "heads", "kv"),
            "wo": L + ("heads", "kv", "embed"),
            "mlp_norm": L + (None,),
            "w_gate": L + ("embed", "mlp"),
            "w_up": L + ("embed", "mlp"),
            "w_down": L + ("mlp", "embed"),
        },
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }


def init(config: LlamaConfig, key) -> Dict[str, Any]:
    c = config
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * (fan_in ** -0.5)).astype(c.dtype)

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


def _remat_policy(config):
    """Map config.remat_policy to a jax.checkpoint policy (None = full)."""
    name = getattr(config, "remat_policy", "full")
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name == "dots_attn":
        # "dots" + save the flash-attention outputs by name: pallas_call is
        # not a dot, so under plain "dots" the whole attention forward
        # kernel reruns inside the backward pass. Saving it costs
        # B*S*H*D bf16 per layer (64 MB at bench shapes).
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names("attn_out"),
        )
    return None


def _rms_norm(x, weight, eps):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(dtype) * weight


def _qk_norm(q, k, params, config):
    """With `config.qk_norm`: one RMSNorm over ALL channels of the q
    projection [B, S, H, D] (scale `q_norm` [H, D]) and one over all of the
    k projection, not per head, before RoPE. Otherwise q and k as given."""
    if not config.qk_norm:
        return q, k

    def norm(x, weight):
        b, s, h, d = x.shape
        return _rms_norm(x.reshape(b, s, h * d), weight.reshape(h * d),
                         config.norm_eps).reshape(b, s, h, d)

    return norm(q, params["q_norm"]), norm(k, params["k_norm"])


def _rope(x, positions, theta):
    # x: [B, S, H, D]; rotate pairs (d, d + D/2).
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions[:, :, None].astype(jnp.float32) * freqs[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]  # [B, S, 1, half]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    ).astype(x.dtype)


def _attention(q, k, v, config: LlamaConfig, mesh=None):
    if config.use_ring_attention and mesh is not None and mesh.shape.get("sp", 1) > 1:
        from ray_tpu.parallel.ring_attention import ring_attention_sharded

        rep = config.n_heads // config.n_kv_heads
        if rep > 1:
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return ring_attention_sharded(q, k, v, mesh, causal=True)
    if mesh is not None and any(
        mesh.shape.get(a, 1) > 1 for a in ("dp", "fsdp", "tp")
    ):
        from ray_tpu.ops.flash_attention import flash_attention_sharded

        return flash_attention_sharded(q, k, v, mesh, causal=True)
    return flash_attention(q, k, v, causal=True)


def _attn_sublayer(x, params, positions, config: LlamaConfig, mesh=None,
                   rules: Optional[LogicalAxisRules] = None,
                   kv_cache=None, lengths=None):
    """Pre-norm attention block shared by the training layer, the KV-cache
    decode path and mixtral. With kv_cache=(k_cache, v_cache) it scatters
    the new K/V at `positions` and attends over the cache, returning
    (x, (new_k_cache, new_v_cache)); otherwise returns (x, None)."""
    c = config
    lc = partial(with_logical_constraint, mesh=mesh, rules=rules)
    h = _rms_norm(x, params["attn_norm"], c.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, params["wv"])
    q, k = _qk_norm(q, k, params, c)
    q = lc(q, ("batch", "seq", "act_heads", "act_kv"))
    k = lc(k, ("batch", "seq", "act_heads", "act_kv"))
    q = _rope(q, positions, c.rope_theta)
    k = _rope(k, positions, c.rope_theta)
    new_cache = None
    if kv_cache is not None:
        # Prefill path (decode S=1 goes through _attn_sublayer_decode):
        # additive one-hot scatter at each row's offset (target slots are
        # still zero in append-only generation) — a single MXU matmul
        # over the padded block.
        k_cache, v_cache = kv_cache
        t = k_cache.shape[1]
        onehot = jax.nn.one_hot(positions, t, dtype=k.dtype)  # [B,S,T]
        k_cache = k_cache + jnp.einsum("bst,bshk->bthk", onehot, k)
        v_cache = v_cache + jnp.einsum("bst,bshk->bthk", onehot, v)
        attn = _cached_attention(q, k_cache, v_cache, lengths, c)
        new_cache = (k_cache, v_cache)
    else:
        attn = _attention(q, k, v, c, mesh)
        attn = _checkpoint_name(attn, "attn_out")
    x = x + jnp.einsum("bshk,hkd->bsd", attn, params["wo"])
    return lc(x, ("batch", "seq", "act_embed")), new_cache


def _mlp_sublayer(x, params, config: LlamaConfig, mesh=None,
                  rules: Optional[LogicalAxisRules] = None):
    """Pre-norm SwiGLU MLP block shared by training and decode paths."""
    c = config
    lc = partial(with_logical_constraint, mesh=mesh, rules=rules)
    h = _rms_norm(x, params["mlp_norm"], c.norm_eps)
    gate = jnp.einsum("bsd,df->bsf", h, params["w_gate"])
    up = jnp.einsum("bsd,df->bsf", h, params["w_up"])
    gate = lc(gate, ("batch", "seq", "act_mlp"))
    ff = jax.nn.silu(gate) * up
    x = x + jnp.einsum("bsf,fd->bsd", ff, params["w_down"])
    return lc(x, ("batch", "seq", "act_embed"))


def _layer(x, params, positions, config: LlamaConfig, mesh=None,
           rules: Optional[LogicalAxisRules] = None):
    x, _ = _attn_sublayer(x, params, positions, config, mesh, rules)
    return _mlp_sublayer(x, params, config, mesh, rules)


def forward_hidden(params, tokens, config: LlamaConfig, mesh=None,
                   rules: Optional[LogicalAxisRules] = None):
    """tokens [B,S] -> final-norm hidden states [B,S,D] (pre-lm_head)."""
    c = config
    lc = partial(with_logical_constraint, mesh=mesh, rules=rules)
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    # Constrain the table's embed dim to the ACTIVATION layout (replicated)
    # before the lookup: a gather from an fsdp-sharded embed dim makes the
    # output D-sharded, and XLA can only reach the (batch, seq, None)
    # activation layout from there via involuntary full rematerialization
    # (replicate-then-repartition). With embed replicated at the gather the
    # reshard to the activation spec is a local slice.
    table = lc(params["embed"], ("vocab", "act_embed"))
    x = table[tokens].astype(c.dtype)
    x = lc(x, ("batch", "seq", "act_embed"))

    layer_fn = partial(_layer, positions=positions, config=c, mesh=mesh,
                       rules=rules)
    if c.remat:
        layer_fn = jax.checkpoint(layer_fn, policy=_remat_policy(c))

    def scan_body(x, layer_p):
        return layer_fn(x, layer_p), None

    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    return _rms_norm(x, params["final_norm"], c.norm_eps)


def forward(params, tokens, config: LlamaConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """tokens: [B, S] int32 -> logits [B, S, vocab] (cast to fp32)."""
    lc = partial(with_logical_constraint, mesh=mesh, rules=rules)
    x = forward_hidden(params, tokens, config, mesh, rules)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    logits = lc(logits, ("batch", "seq", "act_vocab"))
    return logits.astype(jnp.float32)


def chunked_ce(hidden, lm_head, targets, mask=None, chunk: int = 256):
    """Cross-entropy without materializing full [B,S,V] fp32 logits: the
    sequence is scanned in chunks and each chunk's logits are rematerialized
    in the backward pass. At V=32k, S=2048 this cuts peak HBM by ~4 GB per
    8 rows — the difference between batch 8 and 16+ on one v5e chip."""
    b, s, d = hidden.shape
    n = s // chunk
    rem = s - n * chunk

    def body(carry, xs):
        h_ck, t_ck, m_ck = xs
        logits = (h_ck @ lm_head).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, t_ck[..., None], axis=-1)[..., 0]
        return carry + jnp.sum(nll * m_ck), None

    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    h_main = hidden[:, :n * chunk].reshape(b, n, chunk, d).transpose(1, 0, 2, 3)
    t_main = targets[:, :n * chunk].reshape(b, n, chunk).transpose(1, 0, 2)
    m_main = mask[:, :n * chunk].reshape(b, n, chunk).transpose(1, 0, 2)
    total, _ = jax.lax.scan(jax.checkpoint(body), jnp.float32(0.0),
                            (h_main, t_main, m_main))
    if rem:
        total, _ = body(total, (hidden[:, n * chunk:], targets[:, n * chunk:],
                                mask[:, n * chunk:]))
    return total / jnp.maximum(jnp.sum(mask), 1.0)


def init_kv_cache(config: LlamaConfig, batch: int, max_len: int,
                  dtype=None) -> Dict[str, Any]:
    """Per-layer KV cache for incremental decoding: arrays shaped
    [n_layers, batch, max_len, n_kv_heads, d_head] (layer-major so the same
    lax.scan over params['layers'] carries the matching cache slice)."""
    c = config
    dtype = dtype or c.dtype
    shape = (c.n_layers, batch, max_len, c.n_kv_heads, c.d_head)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _cached_attention(q, k_cache, v_cache, lengths, config: LlamaConfig):
    """q: [B,S,H,K] new queries at positions lengths..lengths+S;
    k/v_cache: [B,T,kv,K] full cache (already containing the new keys).
    Masks out cache positions >= lengths+S and enforces causality within
    the new block.

    Decode is HBM-bound on the cache read, so the einsums are grouped-query
    aware: q is reshaped to [B,S,kv,rep,K] and contracted against the bf16
    cache directly (fp32 accumulation via preferred_element_type) — no
    jnp.repeat head broadcast, no materialized fp32 cache copy. At bench
    shapes that cuts per-step cache traffic ~4x."""
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


def _attn_sublayer_decode(x, params, positions, config: LlamaConfig,
                          k_cache, v_cache):
    """Decode-step (S=1) attention block: attends over the cache plus the
    new token's own K/V, returning the new K/V for a deferred top-level
    cache scatter (see forward_with_cache)."""
    c = config
    h = _rms_norm(x, params["attn_norm"], c.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, params["wv"])
    q, k = _qk_norm(q, k, params, c)
    q = _rope(q, positions, c.rope_theta)
    k = _rope(k, positions, c.rope_theta)
    lengths = positions[:, 0]
    attn = _decode_attention(q, k, v, k_cache, v_cache, lengths, c)
    x = x + jnp.einsum("bshk,hkd->bsd", attn, params["wo"])
    return x, (k.astype(k_cache.dtype), v.astype(v_cache.dtype))


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
    transiently — 1/n_layers of a dense cache's resident footprint — and
    logical position t lands at gathered index t, so _cached_attention's
    length masking applies unchanged."""
    c = config
    h = _rms_norm(x, params["attn_norm"], c.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, params["wv"])
    q, k = _qk_norm(q, k, params, c)
    q = _rope(q, positions, c.rope_theta)
    k = _rope(k, positions, c.rope_theta)
    n_blocks, bs, kvh, d = k_pool.shape
    b, s = positions.shape
    blk = jnp.take_along_axis(block_table, positions // bs, axis=1)
    flat = jnp.where(valid, blk * bs + positions % bs, 0)  # 0 = scratch
    kf = k_pool.reshape(n_blocks * bs, kvh, d)
    vf = v_pool.reshape(n_blocks * bs, kvh, d)
    kf = kf.at[flat.reshape(-1)].set(
        k.reshape(b * s, kvh, d).astype(kf.dtype))
    vf = vf.at[flat.reshape(-1)].set(
        v.reshape(b * s, kvh, d).astype(vf.dtype))
    k_pool = kf.reshape(n_blocks, bs, kvh, d)
    v_pool = vf.reshape(n_blocks, bs, kvh, d)
    k_all = jnp.take(k_pool, block_table, axis=0).reshape(
        b, -1, kvh, d)
    v_all = jnp.take(v_pool, block_table, axis=0).reshape(
        b, -1, kvh, d)
    attn = _cached_attention(q, k_all, v_all, lengths, c)
    x = x + jnp.einsum("bshk,hkd->bsd", attn, params["wo"])
    return x, (k_pool, v_pool)


def _attn_sublayer_paged_decode(x, params, positions, config: LlamaConfig,
                                k_pool, v_pool, block_table):
    """Decode-step (S=1) paged attention: gathers each row's KV from the
    pool (positions < lengths only — the pool is READ-ONLY here), adds
    the new token's self-attention term directly, and returns the new
    K/V for a single deferred top-level pool scatter (mirrors
    _attn_sublayer_decode for the dense cache)."""
    c = config
    h = _rms_norm(x, params["attn_norm"], c.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", h, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, params["wv"])
    q, k = _qk_norm(q, k, params, c)
    q = _rope(q, positions, c.rope_theta)
    k = _rope(k, positions, c.rope_theta)
    n_blocks, bs, kvh, d = k_pool.shape
    b = positions.shape[0]
    # gathered index t == logical position t, so length masking applies
    k_all = jnp.take(k_pool, block_table, axis=0).reshape(b, -1, kvh, d)
    v_all = jnp.take(v_pool, block_table, axis=0).reshape(b, -1, kvh, d)
    lengths = positions[:, 0]
    attn = _decode_attention(q, k.astype(k_pool.dtype),
                             v.astype(v_pool.dtype), k_all, v_all,
                             lengths, c)
    x = x + jnp.einsum("bshk,hkd->bsd", attn, params["wo"])
    return x, (k.astype(k_pool.dtype), v.astype(v_pool.dtype))


def forward_with_paged_cache(params, tokens, pool, block_table, lengths,
                             config: LlamaConfig, valid=None):
    """forward_with_cache over a paged pool (see init_paged_kv_cache).

    tokens: [B, S] new tokens at positions lengths..lengths+S; valid:
    optional [B, S] bool for padded prefill tails (invalid positions write
    to the scratch block and are masked from attention by `lengths`).
    -> (logits [B, S, vocab] fp32, new_pool)"""
    c = config
    b, s = tokens.shape
    positions = lengths[:, None] + jnp.arange(s)[None, :]
    if valid is None:
        valid = jnp.ones((b, s), bool)
    table = with_logical_constraint(params["embed"], ("vocab", "act_embed"))
    x = table[tokens].astype(c.dtype)

    if s == 1:
        # Decode fast path (see forward_with_cache): layers only READ
        # the pool; the new K/V comes out as [L,B,1,kv,K] ys and lands
        # in the (donated) pool with one in-place scatter instead of a
        # per-layer full-pool rewrite.
        def decode_body(x, layer_in):
            layer_p, kp, vp = layer_in
            x, (k1, v1) = _attn_sublayer_paged_decode(
                x, layer_p, positions, c, kp, vp, block_table)
            x = _mlp_sublayer(x, layer_p, c)
            return x, (k1, v1)

        x, (k_new, v_new) = jax.lax.scan(
            decode_body, x, (params["layers"], pool["k"], pool["v"]))
        n_blocks, bs = pool["k"].shape[1], pool["k"].shape[2]
        pos = positions[:, 0]
        blk = jnp.take_along_axis(block_table, (pos // bs)[:, None],
                                  axis=1)[:, 0]
        flat = jnp.where(valid[:, 0], blk * bs + pos % bs, 0)  # 0 = scratch
        new_pool = {}
        for name, new_rows in (("k", k_new), ("v", v_new)):
            flat_pool = pool[name].reshape(
                pool[name].shape[0], n_blocks * bs, *pool[name].shape[3:])
            flat_pool = flat_pool.at[:, flat].set(new_rows[:, :, 0])
            new_pool[name] = flat_pool.reshape(pool[name].shape)
        x = _rms_norm(x, params["final_norm"], c.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
        return logits.astype(jnp.float32), new_pool

    def scan_body(x, layer_in):
        layer_p, kp, vp = layer_in
        x, (kp, vp) = _attn_sublayer_paged(
            x, layer_p, positions, c, kp, vp, block_table, lengths, valid)
        x = _mlp_sublayer(x, layer_p, c)
        return x, (kp, vp)

    x, (new_k, new_v) = jax.lax.scan(
        scan_body, x, (params["layers"], pool["k"], pool["v"]))
    x = _rms_norm(x, params["final_norm"], c.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits.astype(jnp.float32), {"k": new_k, "v": new_v}


def forward_with_cache(params, tokens, cache, lengths, config: LlamaConfig):
    """Incremental forward for generation (prefill when S>1, decode at S=1).

    tokens: [B, S] the NEW tokens, logically at positions lengths..lengths+S.
    cache:  dict from init_kv_cache (functionally updated and returned).
    lengths: [B] int32 — number of tokens already in the cache per row.
    -> (logits [B, S, vocab] fp32, new_cache)

    Reference parity note: ray has no inference engine (serving delegates to
    user code / vLLM); this is the TPU-native decode path that
    ray_tpu.inference builds continuous batching on.
    """
    c = config
    b, s = tokens.shape
    positions = lengths[:, None] + jnp.arange(s)[None, :]
    # Same embed-dim constraint as forward_hidden: under an ambient sharded
    # mesh a gather from an fsdp-sharded table forces a full-remat reshard.
    table = with_logical_constraint(params["embed"], ("vocab", "act_embed"))
    x = table[tokens].astype(c.dtype)

    if s == 1:
        # Decode fast path: layers only READ the cache; each layer's new
        # K/V comes out as a tiny [L,B,1,kv,K] ys and is scattered into
        # the (donated) cache once, in place — the per-layer in-scan
        # rewrite would cost a full cache read+write per token.
        def decode_body(x, layer_in):
            layer_p, k_cache, v_cache = layer_in
            x, (k1, v1) = _attn_sublayer_decode(
                x, layer_p, positions, c, k_cache, v_cache)
            x = _mlp_sublayer(x, layer_p, c)
            return x, (k1, v1)

        x, (k_new, v_new) = jax.lax.scan(
            decode_body, x, (params["layers"], cache["k"], cache["v"]))
        b_idx = jnp.arange(b)
        new_cache = {
            "k": cache["k"].at[:, b_idx, lengths].set(
                k_new[:, :, 0], mode="drop"),
            "v": cache["v"].at[:, b_idx, lengths].set(
                v_new[:, :, 0], mode="drop"),
        }
    else:
        def scan_body(x, layer_in):
            layer_p, k_cache, v_cache = layer_in
            x, (k_cache, v_cache) = _attn_sublayer(
                x, layer_p, positions, c, kv_cache=(k_cache, v_cache),
                lengths=lengths)
            x = _mlp_sublayer(x, layer_p, c)
            return x, (k_cache, v_cache)

        x, (new_k, new_v) = jax.lax.scan(
            scan_body, x, (params["layers"], cache["k"], cache["v"]))
        new_cache = {"k": new_k, "v": new_v}
    x = _rms_norm(x, params["final_norm"], c.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    return logits.astype(jnp.float32), new_cache


def loss_fn(params, batch, config: LlamaConfig, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """Next-token cross-entropy. batch: {"tokens": [B, S]} (targets are the
    shifted tokens) or explicit {"inputs", "targets", "mask"}.
    With config.loss_chunk_size > 0 the CE is computed chunk-by-chunk over
    the sequence (see chunked_ce) so full-vocab logits never materialize."""
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
        mask = batch.get("mask")
    else:
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        mask = None
    if config.loss_chunk_size:
        hidden = forward_hidden(params, inputs, config, mesh, rules)
        return chunked_ce(hidden, params["lm_head"], targets, mask,
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


def flops_per_token(config: LlamaConfig, seq_len: int) -> float:
    """Approx training FLOPs/token (fwd+bwd ≈ 6N + attention term)."""
    c = config
    param_flops = 6.0 * c.num_params()
    # Causal attention: QK^T + PV = 2 matmuls × 2 flops × H·D × S/2 (causal
    # average) × 3 (fwd+bwd) = 6·H·D·S per layer per token.
    attn_flops = 6.0 * c.n_layers * c.n_heads * c.d_head * seq_len
    return param_flops + attn_flops
