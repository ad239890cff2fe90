"""A residual path of SEVERAL streams: manifold-constrained hyper-connections
(mHC, arXiv:2512.24880, over Hyper-Connections, arXiv:2409.19606). A piece of
the layer library, beside `blocks.py`: a model module takes it where its
residual state is n copies of the stream that learned maps mix.

The state is X [n, B, S, D], the streams LEADING (a token's n x D values are
n rows of n [B, S, D] slabs: the layout the v5e compiler gives a [B, S, n, D]
array itself, `{3,1,0,2}`, with a copy at every scan boundary where the
logical order is another). One CONNECTION around a sublayer F, a token at a
time, the maps in float32:

    r      = rms of the token's n x D values (`norm_eps` inside the root)
    a      = (vec(X) / r) . phi                      phi [n, D, n + n + n^2]
    H_pre  = sigmoid(alpha[0] a_pre + b_pre)         in (0, 1)^n
    H_post = 2 sigmoid(alpha[1] a_post + b_post)     in (0, 2)^n
    M      = exp(clip(alpha[2] mat(a_res) + b_res, lo, hi))
    `hc_sinkhorn_iters` times: M /= rowsum(M) + hc_eps; M /= colsum(M) + hc_eps
    h      = sum_i H_pre[i] X[i]
    y      = F(h)                  (F has its own pre-norm)
    X'[i]  = sum_j M[i, j] X[j] + H_post[i] y

`expand` copies a stream into the n (the path's start), `reduce` sums them
(its end). The maps are computed with the tokens as ONE minor dim,
[n + n + n^2, B S] and [n, n, B S]: a [.., 4, 4] minor pair pads 32-fold in
the chip's tiles, and with [.., B, S] kept apart through Sinkhorn's steps
the v5e compiler refuses Xing4.0's step at the depth it places this way
("Used 15.95G of 15.75G hbm"; PERF.md section 6, PR 61). vec(X) . phi is X's
own bf16 values against phi's with float32 sums, times 1 / r afterwards (a
scalar a token), so no normed copy of X is made.

TWO FORMS of a connection, told apart by what the code can observe and by
nothing else (`ops/stream_mix.fused`: a TPU, bf16 streams, D a multiple of
128, the tokens a multiple of the token tile, blocks that fit a call's
default VMEM, one device). Where it holds, a connection is FOUR PALLAS CALLS,
two forward and two backward (`ops/stream_mix.py`, PR 62): `hc.pre` reads X
once and writes h and the maps, `hc.post` reads X, y and the maps and writes
X'; on the way back `hc.post`'s call writes dy and the maps' cotangents,
`hc.pre`'s recomputes Sinkhorn's iterates in VMEM and writes dX once. The
maps `maps` returns are that first call's outputs, not a second computation.
Elsewhere, and as the tests' oracle, plain `jnp` as below: the pre-mix, the
post-mix and their transposes are passes over X that the compiler fuses as it
can. `benchmarks/opcount_xing.hc_bytes` is the path's roofline; the four
calls make 37 passes of [tokens, D] where it counts 20 (the sublayer sits
between the pre-mix and the post-mix and its cotangent arrives after the
post-mix's backward, so X is read four times).

A config gives `hc_sinkhorn_iters`, `hc_eps`, `h_res_clamp_min` / `_max` (lo,
hi) and `norm_eps`; n is X's leading dim. Scopes `hc.expand`, `hc.maps`,
`hc.pre`, `hc.post`, `hc.reduce` (a Pallas call's events are named by its
scope: `%hc.pre...`); counters per LOWERING `hc.connections`,
`hc.sinkhorn_iters`, `hc.rows_mixed` (tokens x n a connection) and
`hc.rows_fused` (the same, of the connections that took the Pallas calls).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu._private import device_profiler
from ray_tpu.models import blocks
from ray_tpu.ops import stream_mix
from ray_tpu.parallel.sharding import LogicalAxisRules


def n_maps(n: int) -> int:
    """phi's outputs: H_pre's n, H_post's n, H_res's n x n."""
    return n + n + n * n


def connection_num_params(n: int, d_model: int) -> int:
    return n * d_model * n_maps(n) + 3 + n_maps(n)


def connection_axes(L):
    """One connection's parameters under the leading axes `L`, whole on
    every chip."""
    return {"phi": L + (None, None, None), "alpha": L + (None,),
            "b": L + (None,)}


def init_connection(config, key):
    """phi fan-in scaled over a token's n x D values (a ~ N(0, 1) a map),
    alpha = 1 and b ~ N(0, 1), both float32: NOT a training recipe's small
    alpha, under which every token has nearly the same maps; here they
    differ by token, H_res is no permutation and no uniform matrix, and a
    comparison with a reference sees the mechanism."""
    c = config
    n = c.hc_mult
    k_phi, k_b = jax.random.split(key)
    return {"phi": blocks.dense(c, k_phi, (n, c.d_model, n_maps(n)),
                                n * c.d_model),
            "alpha": jnp.ones((3,), jnp.float32),
            "b": jax.random.normal(k_b, (n_maps(n),), jnp.float32)}


def expand(x, n: int, mesh=None, rules: Optional[LogicalAxisRules] = None):
    """x [B, S, D] -> X [n, B, S, D], every stream a copy."""
    with jax.named_scope("hc.expand"):
        return blocks.residual(jnp.broadcast_to(x[None], (n,) + x.shape),
                               mesh, rules)


def reduce(X):
    """X [n, B, S, D] -> the streams' sum [B, S, D], in float32, rounded
    once."""
    with jax.named_scope("hc.reduce"):
        return jnp.sum(X.astype(jnp.float32), axis=0).astype(X.dtype)


def sinkhorn(M, iters: int, eps: float):
    """M [n, n, ...] positive -> its rows (over dim 1), then its columns
    (over dim 0), normalised `iters` times, `eps` in every denominator."""
    for _ in range(iters):
        M = M / (jnp.sum(M, axis=1, keepdims=True) + eps)
        M = M / (jnp.sum(M, axis=0, keepdims=True) + eps)
    return M


def _maps(X, p, config):
    """`maps` in plain `jnp`."""
    c = config
    n, b, s, _ = X.shape
    with jax.named_scope("hc.maps"):
        square = X.astype(jnp.float32)
        inv_rms = jax.lax.rsqrt(
            jnp.mean(square * square, axis=(0, 3)) + c.norm_eps)
        a = (jnp.einsum("nbsd,ndm->mbs", X, p["phi"],
                        preferred_element_type=jnp.float32)
             * inv_rms).reshape(n_maps(n), b * s)
        alpha, bias = p["alpha"], p["b"][:, None]
        pre = jax.nn.sigmoid(alpha[0] * a[:n] + bias[:n])
        post = 2 * jax.nn.sigmoid(alpha[1] * a[n:2 * n] + bias[n:2 * n])
        res = jnp.exp(jnp.clip(alpha[2] * a[2 * n:] + bias[2 * n:],
                               c.h_res_clamp_min, c.h_res_clamp_max))
        res = sinkhorn(res.reshape(n, n, b * s), c.hc_sinkhorn_iters,
                       c.hc_eps)
        return (pre.reshape(n, b, s), post.reshape(n, b, s),
                res.reshape(n, n, b, s))


def pre_mix(X, p, config, mesh=None):
    """The first half of connection `p`: X [n, B, S, D] -> (h [B, S, D],
    what `post_mix` and `maps` take: X and the maps, as rows of
    `ops/stream_mix.py` where its calls ran, else as `maps` returns them)."""
    n, b, s, d = X.shape
    device_profiler.count("hc.sinkhorn_iters", config.hc_sinkhorn_iters)
    if stream_mix.fused(X, mesh):
        with jax.named_scope("hc.pre"):
            h, rows, X = stream_mix.pre_mix(
                X.reshape(n, b * s, d), stream_mix.phi_rows(p["phi"]),
                stream_mix.coef_rows(p["alpha"], p["b"], n),
                stream_mix.spec_of(config, n), stream_mix.INTERPRET)
        return h.reshape(b, s, d), (X.reshape(n, b, s, d), rows)
    mixed = _maps(X, p, config)
    with jax.named_scope("hc.pre"):
        h = sum(mixed[0][i][..., None] * X[i].astype(jnp.float32)
                for i in range(n)).astype(X.dtype)
    return h, (X, mixed)


def post_mix(mixed, y):
    """`pre_mix`'s second result and y = F(h) [B, S, D] -> X' [n, B, S, D]."""
    X, mapped = mixed
    n = X.shape[0]
    with jax.named_scope("hc.post"):
        if not isinstance(mapped, tuple):
            return stream_mix.post_mix(
                X.reshape(n, -1, X.shape[-1]), y.reshape(-1, y.shape[-1]),
                mapped, stream_mix.INTERPRET).reshape(X.shape)
        f32 = jnp.float32
        _, post, res = (m[..., None] for m in mapped)
        # column j of H_res [n, B, S, 1] against stream j [B, S, D]: all n
        # rows of X' leave ONE elementwise pass (a stack of n rows formed
        # apart costs a pass more to put them together)
        return (sum(res[:, j] * X[j].astype(f32) for j in range(n))
                + post * y.astype(f32)).astype(X.dtype)


def maps(X, p, config, mesh=None):
    """X [n, B, S, D] -> (H_pre [n, B, S], H_post [n, B, S], H_res
    [n, n, B, S]) of connection `p`, float32; inside, the tokens are one
    dim. The maps `connect` mixes with: `pre_mix`'s."""
    n, b, s, _ = X.shape
    mapped = pre_mix(X, p, config, mesh)[1][1]
    if isinstance(mapped, tuple):
        return mapped
    flat = stream_mix.maps_of(mapped, n)
    return (flat[:n].reshape(n, b, s), flat[n:2 * n].reshape(n, b, s),
            flat[2 * n:].reshape(n, n, b, s))


def connect(X, p, branch, config, mesh=None,
            rules: Optional[LogicalAxisRules] = None):
    """One connection: X [n, B, S, D] -> (X' [n, B, S, D], aux) around
    `branch`: h [B, S, D] -> (F(h) [B, S, D], aux), the sublayer WITH its
    pre-norm and without its residual add (`mixers.mla_mixer`,
    `blocks.gated_mlp`, `experts.expert_parts` of the normed h)."""
    if mesh is not None and any(mesh.shape.get(a, 1) > 1
                                for a in ("tp", "sp")):
        raise NotImplementedError(
            "the streams are mixed a token at a time on whole rows: no "
            "`tp` or `sp` mesh axis yet")
    rows = X.shape[0] * math.prod(X.shape[1:3])
    h, mixed = pre_mix(X, p, config, mesh)
    y, aux = branch(h)
    X = post_mix(mixed, y)
    device_profiler.count("hc.connections", 1)  # per lowering
    device_profiler.count("hc.rows_mixed", rows)
    device_profiler.count("hc.rows_fused",
                          0 if isinstance(mixed[1], tuple) else rows)
    return blocks.residual(X, mesh, rules), aux
