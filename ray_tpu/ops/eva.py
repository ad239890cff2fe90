"""EVA attention as EvaByte ships it (Zheng, Yuan, Wang, Kong, "Efficient
Attention via Control Variates", ICLR 2023; EvaByte's released `eva.py`,
whose `eva_prep_kv` / `eva_agg` kernels fix the simplified form: a learned
per-head pooling vector and a learned per-head offset in place of the
paper's sampled features).

With c(j) = j // chunk a byte's chunk and w(n) = n // window a byte's
window, q, k, v after RoPE:

    summaries, a head and chunk c:  p_j = softmax over the chunk's bytes of
                                          (phi . k_j)      (no softmax scale)
        k~_c = sum_j p_j k_j + mu       v~_c = sum_j p_j v_j
    query n, ONE softmax over two kinds of key:
        its window's own bytes {j : w(j) = w(n), j <= n}  scale q_n . k_j, v_j
        every EARLIER window's chunks {c : w(c chunk) < w(n)}
                                                    scale q_n . k~_c, v~_c

Within a query's own window no summary is visible, so no summary a query
sees holds a byte later than itself. There is no scan and no state: the
summaries are one pass over K and V (`summarise`, plain `jnp` in float32
under the scope `eva.summarise`), and the attention is ONE flash call over
the key axis [summaries ; bytes], which is not the query axis, under the
static rule `flash_attention.EvaWindows` (scope `eva.attend`, which names
its Pallas events). dk and dv of the summary rows flow back through the
pooling to k, v, phi and mu by autodiff.

S is whole chunks and `window` is whole chunks; the LAST window may be
partial (its chunks whole): its summaries are keys no query sees.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu._private import device_profiler
from ray_tpu.ops.flash_attention import (
    EvaWindows,
    flash_attention,
    flash_attention_sharded,
)


def summarise(k, v, phi, mu, chunk: int):
    """k, v [B, S, H, D], phi, mu [H, D] -> (k~, v~) [B, S / chunk, H, D]:
    each chunk's keys and values pooled under softmax(phi . k_j) over its
    `chunk` bytes, the keys' pool plus mu. Weights and sums in float32,
    rounded once to the operands' dtypes."""
    b, s, h, d = k.shape
    if s % chunk:
        raise ValueError(f"S {s} is not whole chunks of {chunk}")
    with jax.named_scope("eva.summarise"):
        f32 = jnp.float32
        kc = k.reshape(b, s // chunk, chunk, h, d).astype(f32)
        vc = v.reshape(b, s // chunk, chunk, h, d).astype(f32)
        p = jax.nn.softmax(
            jnp.sum(kc * phi.astype(f32), axis=-1), axis=2)[..., None]
        k_sum = jnp.sum(p * kc, axis=2) + mu.astype(f32)
        v_sum = jnp.sum(p * vc, axis=2)
        return k_sum.astype(k.dtype), v_sum.astype(v.dtype)


def eva_attention(q, k, v, phi, mu, window: int, chunk: int, scale=None,
                  mesh=None, **flash):
    """q, k, v [B, S, H, D] (after RoPE), phi, mu [H, D] -> o [B, S, H, D]:
    the flash call over concat([k~, k]), concat([v~, v]) under
    `EvaWindows(S, window, chunk)`; over `mesh` (batch and heads sharded,
    `flash_attention_sharded`) where it has more than one device. Counted
    per lowering: `eva.calls` and the rule's kept scores a (batch, head) by
    kind (`eva.scores_local`, `eva.scores_summary`); the tiles its kernels
    run, and run masked, are the flash call's own `flash.steps_*`."""
    s = q.shape[1]
    rule = EvaWindows(s, window, chunk)
    k_sum, v_sum = summarise(k, v, phi, mu, chunk)
    keys = jnp.concatenate([k_sum, k], axis=1)
    values = jnp.concatenate([v_sum, v], axis=1)
    local, summary = rule.kept(s, keys.shape[1])
    device_profiler.count("eva.calls", 1)
    device_profiler.count("eva.scores_local", local)
    device_profiler.count("eva.scores_summary", summary)
    with jax.named_scope(rule.scope):
        if mesh is not None and mesh.size > 1:
            return flash_attention_sharded(q, keys, values, mesh, mask=rule,
                                           scale=scale, **flash)
        return flash_attention(q, keys, values, mask=rule, scale=scale,
                               **flash)


def eva_attention_reference(q, k, v, phi, mu, window: int, chunk: int,
                            scale=None):
    """The definition both paths are tested against: float32, a dense mask
    written from w(.) and c(.) in its own lines, the summaries by an explicit
    softmax a chunk. [B, S, H, D] -> [B, S, H, D] float32."""
    f32 = jnp.float32
    q, k, v, phi, mu = (x.astype(f32) for x in (q, k, v, phi, mu))
    b, s, h, d = q.shape
    scale = d ** -0.5 if scale is None else scale
    n_chunks = s // chunk
    kc = k.reshape(b, n_chunks, chunk, h, d)
    vc = v.reshape(b, n_chunks, chunk, h, d)
    pool = jnp.einsum("bnjhd,hd->bnjh", kc, phi)
    pool = jnp.exp(pool - pool.max(axis=2, keepdims=True))
    pool = pool / pool.sum(axis=2, keepdims=True)
    k_sum = jnp.einsum("bnjh,bnjhd->bnhd", pool, kc) + mu
    v_sum = jnp.einsum("bnjh,bnjhd->bnhd", pool, vc)
    n = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    c = jnp.arange(n_chunks)[None, :]
    own = (j // window == n // window) & (j <= n)          # [S, S]
    earlier = (c * chunk) // window < n // window          # [S, chunks]
    scores = jnp.concatenate(
        [jnp.einsum("bnhd,bchd->bhnc", q, k_sum),
         jnp.einsum("bnhd,bjhd->bhnj", q, k)], axis=-1) * scale
    kept = jnp.concatenate([earlier, own], axis=-1)
    probs = jax.nn.softmax(jnp.where(kept, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhnk,bkhd->bnhd", probs,
                      jnp.concatenate([v_sum, v], axis=1))
