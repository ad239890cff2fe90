"""Kimi Delta Attention: the gated delta rule with a decay per channel.

Per (batch, head), a state S in R^{d_k x d_v}, float32, S_0 = 0, and per
token t (q, k [d_k], v [d_v], g [d_k] <= 0 the log decay, beta in (0, 2):
a caller's own range is its model's, (0, 1) where beta = sigmoid (Ling),
(0, 2) where beta = 2 x sigmoid and I - beta k k^T may have an eigenvalue
below 0 (Solar); for unit k a step contracts the state either way):

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

`kda_recurrence` is that definition, token by token (tests, chip checks).
`kda(q, k, v, g, beta, g_min=) -> o`, all `[B, H, S, D]` (beta `[B, H, S]`),
is its chunked form (chunk `CHUNK` = 64), a `custom_vjp`; `g_min` says what
the caller's model guarantees of g and picks the plan the scores A and B are
formed by (THE CONTRACT, below):

    G_t = sum_{i <= t} g_i inside the chunk (float32), H the chunk's
    incoming state. With u~_t = beta_t (v_t - (Diag(a_t) S_{t-1})^T k_t) the
    step is S_t = Diag(a_t) S_{t-1} + k_t u~_t^T, so
      A[t, i] = beta_t sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])   (i < t)
      T       = (I + A)^-1 Diag(beta)     (A strictly lower; `_solve`: its
                diagonal blocks of 8 by their powers, then blocks in pairs)
      U = T V,  W = T (exp(G) * K),       U~ = U - W H
      B[t, i] = sum_c q_t[c] k_i[c] exp(G_t[c] - G_i[c])          (i <= t)
      O = (exp(G) * Q) H + B U~
      H' = Diag(exp(G_C)) H + (exp(G_C - G) * K)^T U~

Nothing is divided by a cumulative decay (with g down to -5 a step a
chunk's reaches e^-320). exp(G_t - G_i) is not a product of a row's and a
column's factor that both stay finite over 64 tokens, so A and B are formed
around REFERENCES, by one of two plans. THE CONTRACT: `g_min` is a lower
bound on every g a step that the caller's MODEL guarantees, or None.

- The BOUNDED plan, for `g_min` >= -5 (`G_MIN_BOUNDED`; Ling's safe gate):
  a SUB-block (16 rows) at a time around a reference r_I, the cumulative
  decay at the block's middle: rows carry exp(G_t - r_own), within e^+-40
  for g >= -5, columns exp(min(r_I - G_i, 44)): at most 1 for tokens before
  the block, within e^40 inside it, and capped (masked anyway) after it:
  (SUB / 2) x 5 = 40 < `_EXP_CAP`. Four products a chunk. A g below the
  bound the caller gave overflows float32 after a few tokens; nothing looks.
- The ANY-DECAY plan, for any other `g_min` and for None (Kimi Linear's
  softplus gate, Solar): by HALVING (`_halved_scores`). Level s = 32, 16,
  .., 1 serves the pairs t > i that lie in the upper and the lower half of
  one block of 2s, around the lower half's last token: exp(G_t - G_i) =
  exp(G_t - G_ref) exp(G_ref - G_i), BOTH exponents <= 0 whatever g is, each
  a sum of g itself over a window (`_windows`), so nothing overflows and an
  underflow to 0 is the right answer. Six masked products a chunk, no cap,
  no clamp, forward and in both backward walks; measured on the v5e at
  [1, 64, 8192, 128]: 1.48x the bounded plan's three kernels (PERF.md
  section 6, PR 64).

Matmul operands are rounded to the inputs' dtype (bf16 in the model) with
float32 accumulation, except the solve, which is float32 throughout; the
state is carried from chunk to chunk in float32 and rounded only as a
matmul's operand. On a TPU the call is three Pallas kernels, each walking a
(batch x head) group's chunks in sequence with the carried array resident
in VMEM, the group's rows going through the solve two to an MXU pass
(`_solve_rows`): the forward (`_fwd_kernel`: outputs `o` and the FINAL STATE
`f32[b x h, d_k, d_v]`), and for the backward pass `_states_kernel` (the
forward again less `o`, handing on each chunk's U~, (I + A)^-1 and incoming
state) and `_bwd_kernel` (the chunks walked backwards, the state's
cotangent carried; its docstring has the equations). Their output
signatures are three-dim arrays and no flash kernel's: the benchmark's
queries tell kernels apart by them. Elsewhere the same arithmetic in `jnp`
(`_kda_chunked`, a `lax.scan` over chunks) and XLA's transpose of it.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu._private import device_profiler

CHUNK = 64
SUB = 16
# over (SUB / 2) tokens x |g| <= 5 = 40, which g = -5 throughout reaches
# exactly: at a tie `minimum` hands its gradient half to each side
_EXP_CAP = 44.0
# the least g a step the bounded plan takes: (SUB / 2) x 5 = 40 < _EXP_CAP
G_MIN_BOUNDED = -5.0
_SOLVE_BASE = 8
# (batch, head) rows a grid step: their chunks are independent chains of
# small dependent matmuls, and the MXU waits less the more of them a step
# interleaves (PERF.md section 6, PR 40: 8 is a third faster than 4; at 16
# the backward's second walk does not fit VMEM)
_HEADS_PER_STEP = 8
# saved by a layer's remat policy beside the flash call's (`mla_moe`)
RESIDUAL_NAMES = ("kda.o",)
_HIGHEST = jax.lax.Precision.HIGHEST


def kda_recurrence(q, k, v, g, beta):
    """The definition, token by token, float32 arithmetic -> (o [B, H, S,
    d_v] float32, the final state [B, H, d_k, d_v]). The tokens are walked
    in blocks under `jax.checkpoint`, so its gradient keeps a state a block
    and not one a token."""
    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhkv,bhk->bhv", state, k_t, precision=_HIGHEST))
        state = state + k_t[..., None] * u[..., None, :]
        o_t = jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=_HIGHEST)
        return state, o_t

    b, h, s, d_k = q.shape
    block = max(n for n in range(1, CHUNK + 1) if s % n == 0)
    by_block = lambda x: jnp.moveaxis(x.astype(jnp.float32), 2, 0).reshape(  # noqa: E731
        (s // block, block) + x.shape[:2] + x.shape[3:])
    state = jnp.zeros((b, h, d_k, v.shape[-1]), jnp.float32)
    state, o = jax.lax.scan(
        jax.checkpoint(lambda state, xs: jax.lax.scan(step, state, xs)),
        state, tuple(by_block(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 2), state


def _mm(spec, a, b, dtype):
    """einsum with operands rounded to `dtype`, float32 accumulation."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _solve(a, t_pos, i_pos, mm):
    """a [..., C, C] float32, strictly lower triangular -> (I + a)^-1, in
    ten products `mm` of C x C (C 64, `t_pos` / `i_pos` the row's and the
    column's index, broadcastable to a). Or a [..., C, n C], n such matrices
    side by side, `i_pos` each one's own column index and `mm` their n
    products at once: `_solve_rows`.

    A diagonal block d of `_SOLVE_BASE` (8) rows is nilpotent, d^8 = 0, so
    (I + d)^-1 = (I - d)(I + d^2)(I + d^4), all blocks at once on the
    block-diagonal part of a. Then blocks pair up, three times over:
    [[P, 0], [C, Q]]^-1 = [[P^-1, 0], [-Q^-1 C P^-1, Q^-1]], that is
    t - t c t with t the pairs' inverses so far and c the pairs' lower-left
    blocks of a.

    NOT the shorter (I - a)(I + a^2) ... (I + a^32) over the whole chunk:
    the powers of a GROW before they vanish. With keys that point one way
    and beta near 1, a is near the all-ones strictly lower matrix L, L^32
    has entries of C(62, 31) ~ 4.5e17, and the product that should cancel
    them to entries of order 1 keeps no digit (o off by 1e5 to 1e20 of
    itself at cos(k_t, k_i) 0.5 to 1, beta 0.99) or overflows float32.
    It takes less: a^32's entries go as (beta cos)^32 C(62, 31), and a
    cosine of 0.3 to 0.5 at beta ~0.55 over some chunks, where ten training
    steps took a layer, read 9e8 and an inverse off by 1e3 (PERF.md section
    6, PR 39: that seed's loss went NaN). The inverse itself is tame:
    T Diag(beta)
    maps v to u~, and |u~_t| <= |v_t| + sum_{i < t} |v_i| because every
    step contracts the state (|1 - beta (k . k)| <= 1 for beta in (0, 2) and
    unit k). Here a block's powers stop at d^7. At beta <= 1 their entries
    are at most C(6, 3) = 20 (d^4 of the all-ones block); at beta < 2 they
    carry beta^n more, 16 x 20 = 320 in d^4 and 2^7 in d^7's one entry,
    while (I + beta L)^-1 = (I - N)(I - (1 - beta) N)^-1 (N the shift, L the
    all-ones strictly lower block) has entries of at most 2: the product
    cancels 320 down to 2, which costs under 8 of float32's 24 bits. The
    pairing multiplies inverses, whose entries stay of order 1 (2 at beta
    2): sums of 8 to 32 terms of at most 8. Held on the chip at beta
    1.9-1.999 on aligned keys (`tools/kda_chip_check.py`,
    `negative_eigenvalues`), where the product of the chunk's powers is
    NaN."""
    c = a.shape[-2]
    issued = math.prod(a.shape[:-2])

    def times(x, y):
        # per lowering: the C x C products a call stands for, the calls made
        device_profiler.count("kda.solve_products", issued * (a.shape[-1] // c))
        device_profiler.count("kda.solve_passes_packed", issued)
        return mm(x, y)

    same = lambda size: t_pos // size == i_pos // size  # noqa: E731
    x = jnp.where(same(_SOLVE_BASE), -a, 0.0)
    t = jnp.where(t_pos == i_pos, 1.0, 0.0) + x
    n = 2
    while n < _SOLVE_BASE:
        x = times(x, x)
        t = t + times(t, x)
        n *= 2
    size = _SOLVE_BASE
    while size < c:
        # a is strictly lower: of a pair's off-diagonal blocks only the
        # lower-left one is not zero
        pair = jnp.where(same(2 * size) & ~same(size), a, 0.0)
        t = t - times(times(t, pair), t)
        size *= 2
    return t


def _references(cum):
    """cum [..., C, D] -> (each token's own sub-block reference [..., C, D],
    the sub-blocks' references [..., C / SUB, 1, D])."""
    refs = cum[..., SUB // 2 - 1::SUB, :]
    return jnp.repeat(refs, SUB, axis=-2), refs[..., None, :]


def _chunked(x, n):
    return x.reshape(x.shape[:2] + (n, CHUNK) + x.shape[3:])


def _kda_chunked(q, k, v, g, beta, bounded=True):
    """The chunked form in `jnp` -> (o [B, H, S, d_v] in v.dtype, the final
    state float32). S is padded to a multiple of the chunk with tokens that
    leave the state as it is (g = 0, beta = 0). The scores under the
    bounded plan's sub-block references, or (`bounded` False) the kernels'
    own `_halved_scores` on the chunks as rows."""
    dtype = q.dtype
    b, h, s, d_k = q.shape
    pad = -s % CHUNK
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        q, k, v, g = (jnp.pad(x, widths) for x in (q, k, v, g))
        beta = jnp.pad(beta, widths[:3])
    n = (s + pad) // CHUNK
    f32 = jnp.float32
    qc, kc, vc = (_chunked(x, n).astype(f32) for x in (q, k, v))
    beta = _chunked(beta.astype(f32), n)
    if bounded:
        cum = jnp.cumsum(_chunked(g.astype(f32), n), axis=-2)
        own, refs = _references(cum)
        row = jnp.exp(cum - own)
        # [B, H, N, blocks, C, D]: the columns as each sub-block of rows
        # sees them
        col = kc[..., None, :, :] * jnp.exp(jnp.minimum(
            refs - cum[..., None, :, :], _EXP_CAP))
        blocks = lambda x: x.reshape(  # noqa: E731
            x.shape[:3] + (CHUNK // SUB, SUB, d_k))
        scores = lambda rows: _mm(  # noqa: E731
            "bhnitd,bhnijd->bhnitj", blocks(rows * row), col, dtype).reshape(
                rows.shape[:3] + (CHUNK, CHUNK))
        t_pos = jnp.arange(CHUNK)[:, None]
        i_pos = jnp.arange(CHUNK)[None, :]
        a = jnp.where(t_pos > i_pos, scores(kc), 0.0) * beta[..., None]
        qk = jnp.where(t_pos >= i_pos, scores(qc), 0.0)
    else:
        rows = lambda x: x.reshape((-1,) + x.shape[3:])  # noqa: E731
        t = _halved_scores(
            rows(qc), rows(kc), rows(_chunked(g.astype(f32), n)),
            rows(beta)[:, None, :], _mm_in(dtype), _window_sums_linear)
        back = lambda x: x.reshape(kc.shape[:3] + x.shape[1:])  # noqa: E731
        cum, qk = back(t["cum"]), back(t["qk"])
        a = back(t["araw"]) * beta[..., None]
        t_pos = jnp.arange(CHUNK)[:, None]
        i_pos = jnp.arange(CHUNK)[None, :]
    t = _solve(a, t_pos, i_pos, partial(jnp.matmul, precision=_HIGHEST)) \
        * beta[..., None, :]
    u = _mm("bhnti,bhniv->bhntv", t, vc, dtype)
    w = _mm("bhnti,bhnid->bhntd", t, kc * jnp.exp(cum), dtype)
    last = cum[..., -1:, :]
    q_in = qc * jnp.exp(cum)
    k_out = kc * jnp.exp(last - cum)

    def chunk(state, x):
        u, w, qk, q_in, k_out, decay = x
        new = u - _mm("bhtd,bhdv->bhtv", w, state, dtype)
        o = _mm("bhtd,bhdv->bhtv", q_in, state, dtype) \
            + _mm("bhti,bhiv->bhtv", qk, new, dtype)
        state = state * decay[..., None] \
            + _mm("bhtd,bhtv->bhdv", k_out, new, dtype)
        return state, o

    along = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731
    state = jnp.zeros((b, h, d_k, v.shape[-1]), f32)
    state, o = jax.lax.scan(chunk, state, tuple(
        along(x) for x in (u, w, qk, q_in, k_out, jnp.exp(last[..., 0, :]))))
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * CHUNK, -1)[:, :, :s]
    return o.astype(v.dtype), state


# --------------------------------------------------------------------------
# Pallas kernels
# --------------------------------------------------------------------------

def _mm_in(dtype):
    """The kernels' matmul, batched over the leading (row) axis: a's axis
    1 + dim_a against b's axis 1 + dim_b, operands rounded to `dtype` with
    float32 accumulation, or (`exact`) float32 throughout."""
    def mm(a, b, dim_a, dim_b, exact=False):
        if not exact:
            a, b = a.astype(dtype), b.astype(dtype)
        return jax.lax.dot_general(
            a, b, (((1 + dim_a,), (1 + dim_b,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
            precision=_HIGHEST if exact else None)
    return mm


def _eye(r, n):
    return jax.lax.broadcasted_iota(jnp.int32, (r, n, n), 1) \
        == jax.lax.broadcasted_iota(jnp.int32, (r, n, n), 2)


def _column(row, eye):
    """row [R, 1, N] -> [R, N, 1]: a masked sum, the transpose of a vector
    that Mosaic takes everywhere (`_as_row` is the way back)."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=2, keepdims=True)


def _as_row(column, eye):
    return jnp.sum(jnp.where(eye, column, 0.0), axis=1, keepdims=True)


def _chunk_terms(q, k, g, beta_row, mm, bounded=True):
    """What each kernel forms first of a chunk of R rows: q (or None), k,
    g [R, C, D] float32, beta_row [R, 1, C] -> a dict of the cumulative log
    decay `cum`, the masked score matrices `araw` (k k^T under the decay,
    strictly lower) and `qk` (q k^T, lower), `beta_col` [R, C, 1] and what
    the backward walk takes the scores' gradient back through: under the
    BOUNDED plan the sub-block factors (`row` [R, C, D], `cols`: k under
    each sub-block's column factor, `colfac`: the factors), under the
    any-decay plan `_halved_scores`' levels."""
    if not bounded:
        return _halved_scores(q, k, g, beta_row, mm)
    f32 = jnp.float32
    r, c, d = k.shape
    t_pos = jax.lax.broadcasted_iota(jnp.int32, (r, c, c), 1)
    i_pos = jax.lax.broadcasted_iota(jnp.int32, (r, c, c), 2)
    eye = t_pos == i_pos
    # the cumulative log decay: a lower-triangular sum on the MXU, exact
    cum = mm((t_pos >= i_pos).astype(f32), g, 1, 0, exact=True)
    blocks = c // SUB
    refs = [cum[:, i * SUB + SUB // 2 - 1:i * SUB + SUB // 2, :]
            for i in range(blocks)]                              # [R, 1, D]
    block_of = jax.lax.broadcasted_iota(jnp.int32, (r, c, d), 1) // SUB
    own = refs[-1]
    for i in range(blocks - 1):
        own = jnp.where(block_of == i, refs[i], own)
    row = jnp.exp(cum - own)
    rows = k * row if q is None else \
        jnp.concatenate([k * row, q * row], axis=1)              # [R, 2C, D]
    colfac = [jnp.exp(jnp.minimum(refs[i] - cum, _EXP_CAP))
              for i in range(blocks)]
    cols = [k * f for f in colfac]
    # [R, 2C, C] (k's rows, then q's): sub-block i of rows from product i
    row_block = t_pos // SUB
    row_block2 = row_block if q is None else \
        jnp.concatenate([row_block, row_block], axis=1)
    scores = jnp.zeros(rows.shape[:2] + (c,), f32)
    for i in range(blocks):
        scores = jnp.where(row_block2 == i, mm(rows, cols[i], 1, 1), scores)
    return dict(
        cum=cum, row=row, rows=rows, cols=cols, colfac=colfac,
        row_block2=row_block2, t_pos=t_pos, i_pos=i_pos, eye=eye,
        beta_col=_column(beta_row, eye),
        araw=jnp.where(t_pos > i_pos, scores[:, :c], 0.0),
        qk=None if q is None
        else jnp.where(t_pos >= i_pos, scores[:, c:], 0.0))


def _level_sizes():
    """The halving plan's levels: the half-sizes 32, 16, .., 1 of a chunk."""
    return [CHUNK >> n for n in range(1, CHUNK.bit_length())]


def _windows(t_pos, j_pos):
    """The 0/1 matrices [.., (1 + levels) C, C], stacked over rows, whose
    product with a chunk's g [C, D] is the cumulative log decay and then
    every level's exponent (`_halved_scores`): a token t in the upper half
    of its block of 2s reads sum_{ref < j <= t} g_j = G_t - G_ref, ref the
    lower half's last token; a token i of the lower half sum_{i < j <= ref}
    g_j = G_ref - G_i. Sums of g itself: no difference of two cumulative
    sums, no sign to get wrong."""
    parts = [t_pos >= j_pos]
    for s in _level_sizes():
        ref = t_pos // (2 * s) * (2 * s) + s - 1
        parts.append(((j_pos > ref) & (j_pos <= t_pos))
                     | ((j_pos > t_pos) & (j_pos <= ref)))
    return jnp.concatenate(parts, axis=-2).astype(jnp.bfloat16)


def _window_sums(windows, x, dim):
    """`windows` (0 / 1) contracted over its axis 1 + `dim` with x [R, ., D]
    float32, to float32's accuracy in THREE bf16 passes of the MXU where
    `precision=HIGHEST` takes six: x is split into three bf16 parts that
    add up to it (8 + 8 + 8 bits of mantissa), a 0 / 1 weight is exact in
    bf16 and the accumulation is float32."""
    bf16, out = jnp.bfloat16, None
    for _ in range(3):
        part = x.astype(bf16)
        term = jax.lax.dot_general(
            windows, part, (((1 + dim,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        x = x - part.astype(jnp.float32)
        out = term if out is None else out + term
    return out


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _window_sums_linear(windows, x, dim):
    """`_window_sums` for the `jnp` form, which XLA transposes: left to
    autodiff, the split's casts would hand the cotangent through the first
    bf16 part alone. The transpose of a window sum is a window sum."""
    return _window_sums(windows, x, dim)


_window_sums_linear.defvjp(
    lambda windows, x, dim: (_window_sums(windows, x, dim), windows),
    lambda dim, windows, ct: (jnp.zeros_like(windows),
                              _window_sums(windows, ct, 1 - dim)))


def _level_mask(s, t_pos, i_pos):
    """Level s's pairs: t in the upper half and i in the lower half of ONE
    block of 2s. Over the levels every t > i is in exactly one mask: the
    level of the highest bit in which t and i differ."""
    return (t_pos // s - i_pos // s == 1) & (t_pos // s % 2 == 1)


def _halved_scores(q, k, g, beta_row, mm, window_sums=_window_sums):
    """`_chunk_terms` for ANY g <= 0: A[t, i] and B[t, i] from factors
    whose exponents are all <= 0, so that nothing overflows and an
    underflow to 0 is the right answer. exp(G_t - G_i) = exp(G_t - G_ref)
    exp(G_ref - G_i) for any ref between i and t; by HALVING, level s (32,
    16, .., 1) serves the pairs whose t is in the upper and i in the lower
    half of one block of 2s, with ref the lower half's last token: ONE
    factor a token and level, e = exp(`_windows` . g) in (0, 1] (a row's
    factor where the token is in an upper half, a column's where in a
    lower), one product (x * e)(k * e)^T a level over the whole chunk,
    selected by the level's mask (what it forms elsewhere is finite and
    dropped), log2(C) = 6 products where the bounded plan has C / SUB = 4.
    The diagonal of B is q_t . k_t, no decay. Also kept: `sums`' exponents'
    factors `es` and the stacked rows `x` (k's, then q's), for the backward
    walk (`_halved_scores_back`)."""
    f32 = jnp.float32
    r, c, d = k.shape
    t_pos = jax.lax.broadcasted_iota(jnp.int32, (r, c, c), 1)
    i_pos = jax.lax.broadcasted_iota(jnp.int32, (r, c, c), 2)
    eye = t_pos == i_pos
    # the masks are the same for every row of the grid step: formed once,
    # [1, ., .], and broadcast
    t_one = jax.lax.broadcasted_iota(jnp.int32, (1, c, c), 1)
    i_one = jax.lax.broadcasted_iota(jnp.int32, (1, c, c), 2)
    windows = jnp.broadcast_to(_windows(t_one, i_one),
                               (r, (1 + len(_level_sizes())) * c, c))
    sums = window_sums(windows, g, 1)                            # [R, 7C, D]
    x = k if q is None else jnp.concatenate([k, q], axis=1)
    both = (lambda a: a) if q is None else \
        (lambda a: jnp.concatenate([a, a], axis=1))
    es = []
    scores = jnp.zeros(x.shape[:2] + (c,), f32)
    for n, s in enumerate(_level_sizes()):
        e = jnp.exp(sums[:, (n + 1) * c:(n + 2) * c])
        mask = both(_level_mask(s, t_one, i_one))
        scores = jnp.where(mask, mm(x * both(e), k * e, 1, 1), scores)
        # per lowering: the C x C score products a chunk issues
        device_profiler.count("kda.halving_products", r * (x.shape[1] // c))
        es.append(e)
    qk = None
    if q is not None:
        qk = jnp.where(eye, jnp.sum(q * k, axis=2, keepdims=True),
                       scores[:, c:])
    return dict(
        cum=sums[:, :c], t_pos=t_pos, i_pos=i_pos, eye=eye, windows=windows,
        t_one=t_one, i_one=i_one,
        es=es, x=x, beta_col=_column(beta_row, eye),
        araw=scores[:, :c], qk=qk)


def _halved_scores_back(t, d_scores, q, k, mm):
    """The gradient d_scores [R, 2C, C] (A's rows, then B's) back through
    `_halved_scores` -> (dq, dk [R, C, D], d_sums [R, 6C, D]: the gradient
    of every level's exponent, in `_windows`' order after `cum`)."""
    c = k.shape[1]
    d_diag = jnp.sum(jnp.where(t["eye"], d_scores[:, c:], 0.0), axis=2,
                     keepdims=True)
    d_q, d_k, d_sums = d_diag * k, d_diag * q, []
    for s, e in zip(_level_sizes(), t["es"]):
        mask = _level_mask(s, t["t_one"], t["i_one"])
        block = jnp.where(jnp.concatenate([mask, mask], axis=1), d_scores,
                          0.0)                                   # [R, 2C, C]
        rows, cols = t["x"] * jnp.concatenate([e, e], axis=1), k * e
        d_rows = mm(block, cols, 1, 0)                           # [R, 2C, D]
        d_cols = mm(block, rows, 0, 0)                           # [R, C, D]
        d_k = d_k + (d_rows[:, :c] + d_cols) * e
        d_q = d_q + d_rows[:, c:] * e
        d_rows = d_rows * rows
        d_sums.append(d_rows[:, :c] + d_rows[:, c:] + d_cols * cols)
    return d_q, d_k, jnp.concatenate(d_sums, axis=1)


def _pair_products(x, y, mm):
    """x = [X1 | X2], y = [Y1 | Y2], [P, C, 2C] each -> [X1 Y1 | X2 Y2] in
    ONE product `mm` a pair: y's halves go onto the diagonal of a [2C, 2C]
    weight, and diag(Y1, Y2) adds exact zeros to each of the sums."""
    c = x.shape[1]
    block = lambda axis: jax.lax.broadcasted_iota(  # noqa: E731
        jnp.int32, (x.shape[0], 2 * c, 2 * c), axis) // c
    return mm(x, jnp.where(block(1) == block(2),
                           jnp.concatenate([y, y], axis=1), 0.0))


def _solve_rows(a, t_pos, i_pos, mm):
    """`_solve` for a grid step's R rows, a [R, C, C]. A C x C product
    fills a quarter of the 128 x 128 MXU and costs a whole pass (the weight
    load, the pushes and the pops do not shrink with it), so where R is
    even the rows go through the solve in LANE-PACKED PAIRS, row i beside
    row R / 2 + i as [R / 2, C, 2C], two rows' products to a pass of full
    width: the same float32 arithmetic, bit for bit. R odd (`_specs`' one
    row a step where b x h is odd): `_solve` as it is."""
    r, c, _ = a.shape
    exact = lambda x, y: mm(x, y, 1, 0, exact=True)  # noqa: E731
    if r % 2:
        return _solve(a, t_pos, i_pos, exact)
    wide = (r // 2, c, 2 * c)
    m = _solve(jnp.concatenate([a[:r // 2], a[r // 2:]], axis=2),
               jax.lax.broadcasted_iota(jnp.int32, wide, 1),
               jax.lax.broadcasted_iota(jnp.int32, wide, 2) % c,
               partial(_pair_products, mm=exact))
    return jnp.concatenate([m[:, :, :c], m[:, :, c:]], axis=0)


def _chunk_forward(q, k, v, g, beta_row, state, mm, bounded=True):
    """One chunk -> (o or None where q is, U~, (I + A)^-1, the next
    state), all float32."""
    t = _chunk_terms(q, k, g, beta_row, mm, bounded)
    c, d = k.shape[1:]
    cum = t["cum"]
    m = _solve_rows(t["araw"] * t["beta_col"], t["t_pos"], t["i_pos"], mm)
    solved = m * beta_row
    u = mm(solved, v, 1, 0)
    w = mm(solved, k * jnp.exp(cum), 1, 0)
    new = u - mm(w, state, 1, 0)
    o = None if q is None else \
        mm(q * jnp.exp(cum), state, 1, 0) + mm(t["qk"], new, 1, 0)
    last = cum[:, c - 1:c, :]                                    # [R, 1, D]
    state = state * _decay_column(last) \
        + mm(k * jnp.exp(last - cum), new, 0, 0)
    return o, new, m, state


def _decay_column(last):
    """last [R, 1, D] (a chunk's whole log decay) -> exp(last) as [R, D, 1]."""
    r, _, d = last.shape
    return _column(jnp.exp(last), _eye(r, d))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state_ref, *,
                bounded=True):
    """One chunk of R (batch, head) rows (`_specs`): refs [R, C, D],
    beta [R, N, C] (the row's chunks, this step's picked by the grid's
    index), the state [R, d_k, d_v] float32 resident over the chunk axis.
    `bounded`, here and in the two kernels below: the scores' plan."""
    from jax.experimental import pallas as pl

    n = pl.program_id(1)
    f32 = jnp.float32

    @pl.when(n == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    q, k, v = (r[...].astype(f32) for r in (q_ref, k_ref, v_ref))
    beta_row = beta_ref[:, pl.ds(n, 1), :].astype(f32)           # [R, 1, C]
    o, _, _, state = _chunk_forward(
        q, k, v, g_ref[...], beta_row, state_ref[...], _mm_in(q_ref.dtype),
        bounded)
    o_ref[...] = o.astype(o_ref.dtype)
    state_ref[...] = state


def _states_kernel(k_ref, v_ref, g_ref, beta_ref, new_ref, m_ref, h_ref,
                   state_ref, *, bounded=True):
    """The backward pass's first walk, forwards: what the second needs of
    every chunk and cannot form alone: U~ (`new`, in v's dtype: it only
    ever enters a matmul), (I + A)^-1 (float32 [R, C, C]: the ten exact
    products of the solve are most of a forward chunk's MXU passes) and
    the chunk's INCOMING state (float32). `state_ref` is scratch."""
    from jax.experimental import pallas as pl

    n = pl.program_id(1)
    f32 = jnp.float32

    @pl.when(n == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    k, v = k_ref[...].astype(f32), v_ref[...].astype(f32)
    beta_row = beta_ref[:, pl.ds(n, 1), :].astype(f32)
    state = state_ref[...]
    h_ref[...] = state
    _, new, m, state = _chunk_forward(
        None, k, v, g_ref[...], beta_row, state, _mm_in(k_ref.dtype), bounded)
    new_ref[...] = new.astype(new_ref.dtype)
    m_ref[...] = m
    state_ref[...] = state


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref, new_ref, m_ref,
                h_ref, dstate_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                dh_ref, *, bounded=True):
    """The second walk, BACKWARDS over the chunks (the grid's step j is
    chunk N - 1 - j): the cotangent of the state, `dh_ref` (scratch,
    float32 [R, d_k, d_v]), is carried from a chunk to the one before it.
    In the docstring's names, with H the incoming state, H' the outgoing
    one and M = (I + A)^-1, T = M Diag(beta):

        dU~ = B^T dO + Kd dH'                    Kd = exp(G_C - G) * K
        dB  = dO U~^T (lower), dQg = dO H^T, dKd = U~ dH'^T, dW = -dU~ H^T
        dH  = Qg^T dO + Diag(exp(G_C)) dH' - W^T dU~
        dT  = dU~ V^T + dW Kg^T,  dV = T^T dU~,  dKg = T^T dW
        dA  = -M^T (dT Diag(beta)) M^T (strictly lower)
        dbeta_i = sum_t dT[t, i] M[t, i] + sum_j dA[i, j] Araw[i, j]

    and the score matrices' gradients go back to q, k and G a sub-block of
    rows at a time through the same row and column factors that formed
    them (the references cancel: no term of theirs). dg is the reversed
    cumulative sum of dG, exact. Under the any-decay plan the scores'
    gradients go back a LEVEL at a time (`_halved_scores_back`), and dg is
    `_windows`' transpose on the gradients of G and of every level's
    exponent, in one product (`_window_sums`)."""
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    n = pl.num_programs(1) - 1 - j
    f32 = jnp.float32
    mm = _mm_in(q_ref.dtype)

    @pl.when(j == 0)
    def _():
        dh_ref[...] = dstate_ref[...]

    q, k, v, do = (r[...].astype(f32)
                   for r in (q_ref, k_ref, v_ref, do_ref))
    new = new_ref[...].astype(f32)
    m, h, dh = m_ref[...], h_ref[...], dh_ref[...]
    beta_row = beta_ref[:, pl.ds(n, 1), :].astype(f32)           # [R, 1, C]
    t = _chunk_terms(q, k, g_ref[...], beta_row, mm, bounded)
    r, c, d = k.shape
    cum, eye = t["cum"], t["eye"]
    t_pos, i_pos = t["t_pos"], t["i_pos"]
    decayed = jnp.exp(cum)
    last = cum[:, c - 1:c, :]
    left = jnp.exp(last - cum)
    k_in, q_in, k_out = k * decayed, q * decayed, k * left
    solved = m * beta_row
    w = mm(solved, k_in, 1, 0)

    d_new = mm(t["qk"], do, 0, 0) + mm(k_out, dh, 1, 0)          # [R, C, dv]
    d_qk = jnp.where(t_pos >= i_pos, mm(do, new, 1, 1), 0.0)
    d_q_in = mm(do, h, 1, 1)                                     # [R, C, dk]
    d_k_out = mm(new, dh, 1, 1)
    d_w = -mm(d_new, h, 1, 1)
    decay_col = _decay_column(last)
    d_decay = jnp.sum(h * dh, axis=2, keepdims=True) * decay_col  # [R, dk, 1]
    dh_ref[...] = mm(q_in, do, 0, 0) + dh * decay_col - mm(w, d_new, 0, 0)
    d_solved = mm(d_new, v, 1, 1) + mm(d_w, k_in, 1, 1)          # [R, C, C]
    dv_ref[...] = mm(solved, d_new, 0, 0).astype(dv_ref.dtype)
    d_k_in = mm(solved, d_w, 0, 0)
    d_beta = jnp.sum(d_solved * m, axis=1, keepdims=True)        # [R, 1, C]
    d_a = jnp.where(
        t_pos > i_pos, -mm(mm(m, d_solved * beta_row, 0, 0), m, 1, 1), 0.0)
    d_beta = d_beta + _as_row(
        jnp.sum(d_a * t["araw"], axis=2, keepdims=True), eye)
    dbeta_ref[:, pl.ds(n, 1), :] = d_beta

    d_scores = jnp.concatenate([d_a * t["beta_col"], d_qk], axis=1)
    if not bounded:
        # (`d_last` and `token` as the bounded plan forms them further down:
        # its trace keeps its order, so they are not shared)
        d_last = jnp.sum(d_k_out * k_out, axis=1, keepdims=True) \
            + _as_row(d_decay, _eye(r, d))
        token = jax.lax.broadcasted_iota(jnp.int32, (r, c, d), 1)
        d_q, d_k, d_sums = _halved_scores_back(t, d_scores, q, k, mm)
        dq_ref[...] = (d_q + d_q_in * decayed).astype(dq_ref.dtype)
        dk_ref[...] = (d_k + d_k_in * decayed
                       + d_k_out * left).astype(dk_ref.dtype)
        d_cum = d_q_in * q_in + d_k_in * k_in - d_k_out * k_out \
            + jnp.where(token == c - 1, d_last, 0.0)
        dg_ref[...] = _window_sums(
            t["windows"], jnp.concatenate([d_cum, d_sums], axis=1), 0)
        return
    row, rows = t["row"], t["rows"]
    d_rows = jnp.zeros((r, 2 * c, d), f32)
    d_k = jnp.zeros((r, c, d), f32)
    d_cum = jnp.zeros((r, c, d), f32)
    for i in range(c // SUB):
        block = jnp.where(t["row_block2"] == i, d_scores, 0.0)   # [R, 2C, C]
        d_rows = d_rows + mm(block, t["cols"][i], 1, 0)
        d_col = mm(block, rows, 0, 0)                            # [R, C, D]
        d_k = d_k + d_col * t["colfac"][i]
        d_cum = d_cum - d_col * t["cols"][i]
    d_rows_k, d_rows_q = d_rows[:, :c], d_rows[:, c:]
    dq_ref[...] = (d_rows_q * row + d_q_in * decayed).astype(dq_ref.dtype)
    dk_ref[...] = (d_k + d_rows_k * row + d_k_in * decayed
                   + d_k_out * left).astype(dk_ref.dtype)
    d_cum = d_cum + d_rows_k * rows[:, :c] + d_rows_q * rows[:, c:] \
        + d_q_in * q_in + d_k_in * k_in - d_k_out * k_out
    d_last = jnp.sum(d_k_out * k_out, axis=1, keepdims=True) \
        + _as_row(d_decay, _eye(r, d))
    token = jax.lax.broadcasted_iota(jnp.int32, (r, c, d), 1)
    d_cum = d_cum + jnp.where(token == c - 1, d_last, 0.0)
    dg_ref[...] = mm((t_pos <= i_pos).astype(f32), d_cum, 1, 0, exact=True)


def _padded(q, k, v, g, beta, *more):
    """S padded to a multiple of the chunk with tokens that leave the state
    as it is (g = 0, beta = 0) -> (the arrays flat over (batch, head),
    beta as [rows, chunks, C], the chunks' number)."""
    s = q.shape[2]
    pad = -s % CHUNK
    n = (s + pad) // CHUNK
    flat = lambda x: x.reshape((-1,) + x.shape[2:])  # noqa: E731
    wide = [flat(jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0))))
            for x in (q, k, v, g.astype(jnp.float32)) + more]
    beta = flat(jnp.pad(beta.astype(jnp.float32), ((0, 0), (0, 0), (0, pad))))
    return wide[:4] + [beta.reshape(-1, n, CHUNK)] + wide[4:], n


def _specs(rows, n, reverse=False):
    """-> (rows a step, `walked(d)`: a [rows, S, d] array's chunk of this
    step, `held(*dims)`: a block that stays over the chunk axis)."""
    from jax.experimental import pallas as pl

    per = math.gcd(rows, _HEADS_PER_STEP)
    at = (lambda j: n - 1 - j) if reverse else (lambda j: j)
    walked = lambda d, c=CHUNK: pl.BlockSpec(  # noqa: E731
        (per, c, d), lambda i, j: (i, at(j), 0))
    held = lambda *dims: pl.BlockSpec(  # noqa: E731
        (per,) + dims, lambda i, j: (i, 0, 0))
    return per, walked, held


def _kernel(kernel, bounded):
    """`kernel` under its plan, counted per lowering: `kda.kernels`, and
    `kda.kernels_any_decay` those of the any-decay plan. The bounded plan's
    is the function itself: its name, and so its lowering, as before."""
    device_profiler.count("kda.kernels", 1)
    device_profiler.count("kda.kernels_any_decay", 0 if bounded else 1)
    return kernel if bounded else partial(kernel, bounded=False)


def _params(bounded=True):
    from jax.experimental.pallas import tpu as pltpu

    # the any-decay backward walk keeps six levels' factors beside what the
    # bounded one keeps: 16.9 MiB of stack at 8 rows a step, over the
    # compiler's default of 16 (a v5e's VMEM is 128 MiB)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        **({} if bounded else {"vmem_limit_bytes": 32 * 2 ** 20}))


@partial(jax.jit, static_argnames=("interpret", "bounded"))
def _kda_fwd_pallas(q, k, v, g, beta, interpret=False, bounded=True):
    """-> (o [B, H, S, d_v] in v.dtype, the final state [B, H, d_k, d_v]
    float32): the grid walks (groups of batch x head rows, chunks), the
    chunk axis in sequence."""
    from jax.experimental import pallas as pl

    b, h, s, d_k = q.shape
    d_v = v.shape[-1]
    args, n = _padded(q, k, v, g, beta)
    rows = b * h
    per, walked, held = _specs(rows, n)
    o, state = pl.pallas_call(
        _kernel(_fwd_kernel, bounded),
        grid=(rows // per, n),
        in_specs=[walked(d_k), walked(d_k), walked(d_v), walked(d_k),
                  held(n, CHUNK)],
        out_specs=[walked(d_v), held(d_k, d_v)],
        out_shape=[jax.ShapeDtypeStruct((rows, n * CHUNK, d_v), v.dtype),
                   jax.ShapeDtypeStruct((rows, d_k, d_v), jnp.float32)],
        compiler_params=_params(bounded),
        interpret=interpret,
    )(*args)
    return (o.reshape(b, h, n * CHUNK, d_v)[:, :, :s],
            state.reshape(b, h, d_k, d_v))


@partial(jax.jit, static_argnames=("interpret", "bounded"))
def _kda_bwd_pallas(q, k, v, g, beta, do, dstate, interpret=False,
                    bounded=True):
    """The five gradients from TWO calls: `_states_kernel` forwards, then
    `_bwd_kernel` backwards. Their outputs are three-dim arrays, (bf16,
    f32, f32) and (bf16, bf16, bf16, f32, f32): no flash kernel's and not
    the forward's (the benchmark's queries tell kernels apart by these)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, s, d_k = q.shape
    d_v = v.shape[-1]
    (q_, k_, v_, g_, beta_, do_), n = _padded(q, k, v, g, beta, do)
    rows = b * h
    f32 = jnp.float32
    shaped = jax.ShapeDtypeStruct
    per, walked, held = _specs(rows, n)
    new, m, states = pl.pallas_call(
        _kernel(_states_kernel, bounded),
        grid=(rows // per, n),
        in_specs=[walked(d_k), walked(d_v), walked(d_k), held(n, CHUNK)],
        out_specs=[walked(d_v), walked(CHUNK), walked(d_v, d_k)],
        out_shape=[shaped((rows, n * CHUNK, d_v), v.dtype),
                   shaped((rows, n * CHUNK, CHUNK), f32),
                   shaped((rows, n * d_k, d_v), f32)],
        scratch_shapes=[pltpu.VMEM((per, d_k, d_v), f32)],
        compiler_params=_params(bounded),
        interpret=interpret,
    )(k_, v_, g_, beta_)
    per, walked, held = _specs(rows, n, reverse=True)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        _kernel(_bwd_kernel, bounded),
        grid=(rows // per, n),
        in_specs=[walked(d_k), walked(d_k), walked(d_v), walked(d_k),
                  held(n, CHUNK), walked(d_v), walked(d_v), walked(CHUNK),
                  walked(d_v, d_k), held(d_k, d_v)],
        out_specs=[walked(d_k), walked(d_k), walked(d_v), walked(d_k),
                   held(n, CHUNK)],
        out_shape=[shaped((rows, n * CHUNK, d_k), q.dtype),
                   shaped((rows, n * CHUNK, d_k), k.dtype),
                   shaped((rows, n * CHUNK, d_v), v.dtype),
                   shaped((rows, n * CHUNK, d_k), f32),
                   shaped((rows, n, CHUNK), f32)],
        scratch_shapes=[pltpu.VMEM((per, d_k, d_v), f32)],
        compiler_params=_params(bounded),
        interpret=interpret,
    )(q_, k_, v_, g_, beta_, do_, new, m, states,
      dstate.astype(f32).reshape(rows, d_k, d_v))
    tokens = lambda x: x.reshape(b, h, n * CHUNK, -1)[:, :, :s]  # noqa: E731
    return (tokens(dq), tokens(dk), tokens(dv), tokens(dg).astype(g.dtype),
            dbeta.reshape(b, h, n * CHUNK)[:, :, :s].astype(beta.dtype))


# --------------------------------------------------------------------------
# the call
# --------------------------------------------------------------------------

def _forward(q, k, v, g, beta, use_pallas, interpret, bounded):
    n_chunks = -(-q.shape[2] // CHUNK)
    device_profiler.count("kda.chunks", n_chunks)  # per lowering
    if use_pallas or interpret:
        return _kda_fwd_pallas(q, k, v, g, beta, interpret=interpret,
                               bounded=bounded)
    return _kda_chunked(q, k, v, g, beta, bounded)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda(q, k, v, g, beta, use_pallas, interpret, bounded=True):
    """-> (o, the final state [B, H, d_k, d_v] float32): `kda` less its
    defaults (the tests read the state and hand it a cotangent)."""
    return _forward(q, k, v, g, beta, use_pallas, interpret, bounded)


def _kda_fwd_rule(q, k, v, g, beta, use_pallas, interpret, bounded):
    o, state = _forward(q, k, v, g, beta, use_pallas, interpret, bounded)
    return (checkpoint_name(o, RESIDUAL_NAMES[0]), state), (q, k, v, g, beta)


def _kda_bwd_rule(use_pallas, interpret, bounded, res, cotangents):
    """On a TPU the two backward kernels; elsewhere XLA's transpose of the
    chunked arithmetic."""
    if use_pallas or interpret:
        return _kda_bwd_pallas(*res, *cotangents, interpret=interpret,
                               bounded=bounded)
    return jax.vjp(partial(_kda_chunked, bounded=bounded), *res)[1](
        cotangents)


_kda.defvjp(_kda_fwd_rule, _kda_bwd_rule)


def plan_is_bounded(g_min) -> bool:
    """Which plan a caller's guarantee `g_min` picks (`kda`)."""
    return g_min is not None and g_min >= G_MIN_BOUNDED


def kda(q, k, v, g, beta, *, g_min=None, use_pallas=None, interpret=False):
    """q, k [B, H, S, d_k], v [B, H, S, d_v], g [B, H, S, d_k] (log decay a
    step, <= 0), beta [B, H, S] (in (0, 2)) -> o [B, H, S, d_v] in v.dtype.
    `g_min` (static) is what the caller's MODEL guarantees of g: a lower
    bound on every g, or None where it has none. A bound of -5 or above
    takes the bounded plan, anything else the any-decay plan (the module's
    docstring, THE CONTRACT); a g below a bound the caller gave is the
    caller's breach, and nothing here looks.
    `use_pallas=None`: the Pallas kernels on a TPU, `jnp` elsewhere
    (`interpret=True` runs the kernels in the Pallas interpreter)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" and not interpret
    return _kda(q, k, v, g, beta, bool(use_pallas), bool(interpret),
                plan_is_bounded(g_min))[0]
