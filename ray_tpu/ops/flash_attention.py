"""Flash attention: Pallas TPU kernels (fwd + bwd) with a JAX oracle.

Memory-efficient exact attention — O(S) memory via online softmax — the
building block under both the single-chip attention path and (composed with
`parallel.ring_attention` over the sp axis) long-context training. The
kernels follow the Pallas TPU model: Q blocks ride the grid, K/V stream
through VMEM, matmuls hit the MXU in fp32 accumulation
(guide: /opt/skills/guides/pallas_guide.md — grid/BlockSpec, fori_loop,
preferred_element_type).

Layouts: public API takes [B, S, H, D]; kernels run [B, H, S, D].
GQA is handled by repeating KV heads in the wrapper.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# --------------------------------------------------------------------------
# Reference (the oracle tests compare against; the path on non-TPU backends)
# --------------------------------------------------------------------------

def _reference_attention(q, k, v, causal: bool, scale: float):
    # q,k,v: [B,H,S,D]
    s_q, s_k = q.shape[2], k.shape[2]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((s_q, s_k), dtype=bool), k=s_k - s_q)
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# --------------------------------------------------------------------------
# Pallas forward
# --------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_k, seq_q, seq_k):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)  # [block_q, D]
    # Causal with s_q != s_k (decode-style): query i corresponds to key
    # position i + (seq_k - seq_q), matching the oracle's tril(k=s_k-s_q).
    causal_offset = seq_k - seq_q
    q_pos = (qi * block_q + causal_offset
             + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))

    num_kv = pl.cdiv(seq_k, block_k)
    if causal:
        # Only blocks up to (and including) the diagonal contribute.
        num_kv = jnp.minimum(
            num_kv, pl.cdiv((qi + 1) * block_q + causal_offset, block_k)
        )

    def body(j, carry):
        o, m, l = carry
        k_blk = k_ref[0, 0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # [block_q, block_k]
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        # Mask padding rows of a partial final K block (manual dslice reads
        # clamp, duplicating real rows) and, when causal, future positions.
        valid = k_pos < seq_k
        if causal:
            valid = valid & (q_pos >= k_pos)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return o_new, m_new, l_new

    o0 = jnp.zeros_like(q)
    m0 = jnp.full((block_q,), NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((block_q,), dtype=jnp.float32)
    o, m, l = jax.lax.fori_loop(0, num_kv, body, (o0, m0, l0))
    l = jnp.maximum(l, 1e-20)
    o_ref[0, 0] = (o / l[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = (m + jnp.log(l))[:, None]


def _pad_seq(x, block):
    s = x.shape[2]
    pad = (-s) % block
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))


def _flash_fwd_pallas(q, k, v, causal, scale, block_q, block_k, interpret):
    from jax.experimental import pallas as pl

    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    # Pad to block multiples: dynamic_slice CLAMPS out-of-range starts, which
    # would silently shift the last partial block. The kernels mask padded
    # positions via the true seq_q/seq_k.
    q = _pad_seq(q, block_q)
    k = _pad_seq(k, block_k)
    v = _pad_seq(v, block_k)
    s_q_pad, s_k_pad = q.shape[2], k.shape[2]
    grid = (b, h, s_q_pad // block_q)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_q=s_q, seq_k=s_k,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, s_k_pad, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, s_k_pad, d), lambda b_, h_, i: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, s_q_pad, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o[:, :, :s_q], lse[:, :, :s_q]


# --------------------------------------------------------------------------
# Pallas backward
# --------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   scale, causal, block_q, block_k, seq_q, seq_k):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]      # [block_q, 1]
    delta = delta_ref[0, 0]  # [block_q, 1]
    causal_offset = seq_k - seq_q
    q_pos = (qi * block_q + causal_offset
             + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0))

    num_kv = pl.cdiv(seq_k, block_k)
    if causal:
        num_kv = jnp.minimum(
            num_kv, pl.cdiv((qi + 1) * block_q + causal_offset, block_k)
        )

    def body(j, dq):
        k_blk = k_ref[0, 0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.dslice(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        k_pos = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        valid = k_pos < seq_k
        if causal:
            valid = valid & (q_pos >= k_pos)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)
        p = jnp.where(valid, p, 0.0)
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dq = dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dq

    dq = jax.lax.fori_loop(0, num_kv, body, jnp.zeros_like(q))
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q, block_k,
                    seq_q, seq_k):
    from jax.experimental import pallas as pl

    kj = pl.program_id(2)
    k_blk = k_ref[0, 0].astype(jnp.float32)  # [block_k, D]
    v_blk = v_ref[0, 0].astype(jnp.float32)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    causal_offset = seq_k - seq_q

    num_q = pl.cdiv(seq_q, block_q)
    start_q = jnp.int32(0)
    if causal:
        # First q block whose max key position reaches this k block.
        start_q = jnp.maximum(kj * block_k - causal_offset, 0) // block_q

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.dslice(i * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, 0, pl.dslice(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.dslice(i * block_q, block_q), :]
        delta = delta_ref[0, 0, pl.dslice(i * block_q, block_q), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        q_row = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        # Mask padding rows of a partial final Q block; when causal, also
        # mask future keys relative to the offset-shifted query positions.
        valid = q_row < seq_q
        if causal:
            valid = valid & ((q_row + causal_offset) >= k_pos)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)  # [block_q, block_k]
        p = jnp.where(valid, p, 0.0)
        dv = dv + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk, dv

    dk0 = jnp.zeros_like(k_blk)
    dv0 = jnp.zeros_like(v_blk)
    dk, dv = jax.lax.fori_loop(start_q, num_q, body, (dk0, dv0))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                      interpret):
    from jax.experimental import pallas as pl

    b, h, s_q, d = q.shape
    s_k = k.shape[2]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1,
                    keepdims=True)
    # Same padding rationale as the forward (dynamic_slice clamping).
    q = _pad_seq(q, block_q)
    do = _pad_seq(do, block_q)
    lse = _pad_seq(lse, block_q)
    delta = _pad_seq(delta, block_q)
    k = _pad_seq(k, block_k)
    v = _pad_seq(v, block_k)
    s_q_pad, s_k_pad = q.shape[2], k.shape[2]

    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_q=s_q, seq_k=s_k,
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, s_q_pad // block_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, s_k_pad, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, s_k_pad, d), lambda b_, h_, i: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1), lambda b_, h_, i: (b_, h_, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, seq_q=s_q, seq_k=s_k,
    )
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, s_k_pad // block_k),
        in_specs=[
            pl.BlockSpec((1, 1, s_q_pad, d), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, s_q_pad, d), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, s_q_pad, 1), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, s_q_pad, 1), lambda b_, h_, j: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, j: (b_, h_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq[:, :, :s_q], dk[:, :, :s_k], dv[:, :, :s_k]


# --------------------------------------------------------------------------
# custom_vjp wrapper
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhsd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, _ = _flash_fwd_pallas(q, k, v, causal, scale, block_q, block_k, interpret)
    return o


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _flash_fwd_pallas(q, k, v, causal, scale, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd_pallas(
        q, k, v, o, lse, do, causal, scale, block_q, block_k, interpret
    )
    return dq, dk, dv


_flash_bhsd.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(
    q, k, v,
    causal: bool = True,
    scale: Optional[float] = None,
    # Block sizes were chosen on a v5e at B8/H16/D128 seq 2048 in an
    # earlier round (512x1024 fastest; 1024x1024 and k>=1536 exceed VMEM);
    # not re-measured on the current installation. Clamped to seq below.
    block_q: int = 512,
    block_k: int = 1024,
    use_pallas: Optional[bool] = None,
    interpret: bool = False,
):
    """Exact attention over [B, S, H, D] inputs (GQA: fewer KV heads OK).

    Which path runs is read off the platform, never off a failure: with
    `use_pallas=None`, `jax.default_backend() == "tpu"` lowers to the
    Pallas kernels above and any other backend runs the jax.numpy oracle
    (what the CPU test mesh uses; `interpret=True` runs the kernels in
    the Pallas interpreter instead). Nothing falls back from one to the
    other at run time — chip_smoke.py fails unless `tpu_custom_call` is
    in the lowered train step.

    Sequence limit (v5e, libtpu 0.0.34, pinned by
    tests/test_tpu_aot_compile.py): each kernel instance keeps the whole
    sequence's K and V (forward, dq) or q, dO, lse and delta (dk/dv) in
    VMEM, so the backward pass compiles at S 4096 and is refused at
    S 8192 ("Scoped allocation with size 18.98M and limit 16.00M exceeded
    scoped vmem limit"; the forward pass alone still compiles there) —
    at `LlamaConfig.max_seq_len`'s default. Longer sequences need the
    backward pass tiled over the sequence, or `ring_attention` over `sp`.
    """
    b, s_q, h, d = q.shape
    h_kv = k.shape[2]
    if h_kv != h:
        if h % h_kv != 0:
            raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
        rep = h // h_kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if scale is None:
        scale = d ** -0.5
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" and not interpret

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if use_pallas or interpret:
        # Clamp to the sequence, then round DOWN to a lane-aligned multiple
        # of 128 (Mosaic tiling): min(512, 300) = 300 would otherwise make
        # an unaligned BlockSpec. Sequences <=128 keep block == seq, the
        # long-standing short-seq path.
        def _aligned(block, seq):
            b = min(block, seq)
            return (b // 128) * 128 if b > 128 else b

        block_q = _aligned(block_q, s_q)
        block_k = _aligned(block_k, k.shape[1])
        o = _flash_bhsd(qt, kt, vt, causal, scale, block_q, block_k, interpret)
    else:
        o = _reference_attention(qt, kt, vt, causal, scale)
    return o.transpose(0, 2, 1, 3)


def flash_attention_sharded(q, k, v, mesh, causal: bool = True,
                            scale: Optional[float] = None, **kw):
    """shard_map-wrapped flash attention for use inside a pjit-sharded model.

    GSPMD has no partitioning rule for a Pallas custom call, so without this
    wrapper XLA all-gathers q/k/v to every device and replicates the kernel.
    Here batch rides ('dp','fsdp') and heads ride 'tp' explicitly; each shard
    runs the kernel on its local [B/dp·fsdp, S, H/tp, D] block. KV heads are
    repeated to match q heads first so the tp shard is uniform under GQA.
    """
    from jax.sharding import PartitionSpec as P

    h_kv = k.shape[2]
    h = q.shape[2]
    if h_kv != h:
        rep = h // h_kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    # incl. the inter-slice dcn axis: a replicated batch dim would
    # all-gather q/k/v across DCN before every attention call
    batch_axes = tuple(a for a in ("dcn", "dp", "fsdp")
                       if mesh.shape.get(a, 1) > 1)
    batch_div = 1
    for a in batch_axes:
        batch_div *= mesh.shape[a]
    if q.shape[0] % max(batch_div, 1) != 0:
        batch_axes = ()
    head_axis = "tp" if (mesh.shape.get("tp", 1) > 1
                         and h % mesh.shape["tp"] == 0) else None
    spec = P(batch_axes or None, None, head_axis, None)

    fn = functools.partial(flash_attention, causal=causal, scale=scale, **kw)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
