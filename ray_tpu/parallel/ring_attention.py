"""Ring attention: exact attention over sequence-sharded Q/K/V.

Sequence/context parallelism the reference does not implement natively
(SURVEY.md §5 "Long-context / sequence parallelism": Ray only provides the
substrate — NCCL p2p channels — and points users at external Torch libraries).
Here it is a first-class op: K/V blocks rotate around the `sp` mesh axis via
`jax.lax.ppermute` (XLA lowers to ICI collective-permute) while each device
accumulates flash-style online-softmax partial results for its resident Q
block. Communication overlaps compute across ring steps; memory stays
O(S_local) per device, enabling sequences sp× longer than a single chip holds.

Use inside `shard_map` over the `sp` axis (see `ring_attention_sharded` for
the wrapped version).
"""

from __future__ import annotations

import functools
from typing import Optional

NEG_INF = -1e30


def _block_attn_update(q, k, v, o, m, l, q_pos, k_pos, scale, causal):
    """One flash-attention accumulation step against a K/V block.

    q: [B, Sq, H, D]; k, v: [B, Sk, H, D]; o: [B, Sq, H, D];
    m, l: [B, H, Sq] running max / normalizer; *_pos: global token positions.
    """
    import jax.numpy as jnp

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # [B,H,Sq,Sk]
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]           # [Sq, Sk]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))      # [B,H,Sq]
    # Guard fully-masked rows (m_new == NEG_INF) against NaNs.
    m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
    p = jnp.exp(scores - m_safe[..., None])
    p = jnp.where(scores <= NEG_INF / 2, 0.0, p)
    correction = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m) - m_safe)
    correction = jnp.where(m <= NEG_INF / 2, 0.0, correction)
    l_new = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
    return o_new, m_new, l_new


def ring_attention(q, k, v, axis_name: str = "sp", causal: bool = True,
                   scale: Optional[float] = None):
    """Exact attention where q/k/v are the local sequence shard.

    Must run inside shard_map/with an active mesh axis `axis_name`.
    Shapes: q, k, v: [B, S_local, H, D] (GQA: repeat kv heads beforehand).
    Returns [B, S_local, H, D].
    """
    import jax
    import jax.numpy as jnp

    b, s_loc, h, d = q.shape
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    if scale is None:
        scale = d ** -0.5

    q_pos = my_idx * s_loc + jnp.arange(s_loc)
    o = jnp.zeros_like(q, dtype=jnp.float32)
    m = jnp.full((b, h, s_loc), NEG_INF, dtype=jnp.float32)
    l = jnp.zeros((b, h, s_loc), dtype=jnp.float32)

    # Ring: at step s, the local buffer holds K/V originally from device
    # (my_idx - s) mod n; ppermute sends to the right neighbor each step.
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(s, carry):
        o, m, l, k_cur, v_cur = carry
        src = (my_idx - s) % n
        k_pos = src * s_loc + jnp.arange(s_loc)

        def do_update(oml):
            o, m, l = oml
            return _block_attn_update(
                q.astype(jnp.float32), k_cur.astype(jnp.float32),
                v_cur.astype(jnp.float32), o, m, l, q_pos, k_pos, scale,
                causal,
            )

        if causal:
            # Source shards entirely in the future are fully masked — skip
            # their score blocks (roughly halves compute on the sp axis);
            # K/V still rotate so later steps see them.
            o, m, l = jax.lax.cond(
                src <= my_idx, do_update, lambda oml: oml, (o, m, l)
            )
        else:
            o, m, l = do_update((o, m, l))
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return o, m, l, k_nxt, v_nxt

    o, m, l, _, _ = jax.lax.fori_loop(0, n, step, (o, m, l, k, v))
    l = jnp.maximum(l, 1e-20)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name: str = "sp",
                           causal: bool = True):
    """shard_map-wrapped ring attention over sequence-sharded global arrays.

    q/k/v: global [B, S, H, D] logically sharded on S over `axis_name`.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    # Shard batch over every data-parallel axis (incl. the inter-slice dcn
    # axis of multi-slice meshes) and heads over tp — replicating those
    # dims would all-gather the activations (across DCN, for dcn!) and
    # redo attention on every shard, defeating the O(S_local) point.
    batch_axes = tuple(a for a in ("dcn", "dp", "fsdp")
                       if mesh.shape.get(a, 1) > 1)
    bdiv = 1
    for a in batch_axes:
        bdiv *= mesh.shape[a]
    if q.shape[0] % max(bdiv, 1) != 0:
        batch_axes = ()
    head_axis = ("tp" if mesh.shape.get("tp", 1) > 1
                 and q.shape[2] % mesh.shape["tp"] == 0 else None)
    spec = P(batch_axes or None, axis_name, head_axis, None)
    fn = functools.partial(ring_attention, axis_name=axis_name, causal=causal)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
