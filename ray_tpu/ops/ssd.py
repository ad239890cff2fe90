"""Mamba-2's state-space layer (SSD, arXiv:2405.21060): a linear recurrence
with a SCALAR decay a head and no solve.

Per (batch, head), a state H in R^{P x N}, float32, H_0 = 0, and per token t
(x [P], Delta > 0 the step, a = A Delta <= 0 the log decay, B and C [N],
shared by the `H / G` heads of a group):

    H_t = exp(a_t) H_{t-1} + Delta_t x_t B_t^T
    y_t = H_t C_t                                  (+ D x_t, in `ssd`)

`ssd_recurrence` is that definition, token by token (tests, chip checks).
`ssd_scan(x, delta, a, b, c) -> (y, the final state)`, x [B, S, H, P],
delta and a [B, S, H], b and c [B, S, G, N], is its chunked form (`chunk=`
tokens a chunk, `CHUNK` = 128 unless the caller says: the configuration's
`chunk_size`; a chunk does not change the function), a `custom_vjp`. With c_t
the running sum of a inside a chunk, H the chunk's incoming state:

    Y  = ((C B^T) * L) (Delta x) + exp(c_t) C_t H^T,   L[t, s] = exp(c_t - c_s)
                                                       for s <= t, 0 above
    H' = exp(c_Q) H + sum_s exp(c_Q - c_s) Delta_s x_s B_s^T

Every exponent is a difference that is <= 0 (a <= 0, so c falls): nothing
is divided by a cumulative decay and no power of a chunk is multiplied by
another (`ops/kda.py`'s docstring on what that costs). A chunk is plain
matmuls of Q x Q x {P, N} (Q the chunk): C B^T once a GROUP, one product a
head for the part inside the chunk, one each for the state's read and its
update.

Matmul operands are rounded to x's dtype (bf16 in the model) with float32
accumulation; the state is carried from chunk to chunk in float32 and
rounded only as a matmul's operand; the running sums c are float32, made
by XLA before the call (a cumulative sum of [B, S, H] scalars). On a TPU
the call is three Pallas kernels over a grid of (batch x group, chunks),
the chunk axis in sequence with the carried array resident in VMEM, B and C
loaded once a group, the group's heads side by side in the lanes as the
projection leaves them ([B, S, H x P]: no transpose before or after). A
group wider than `_BLOCK_LANES` (1,024 lanes: 16 heads of 64) is walked in
HEAD BLOCKS of that width, each a grid row of its own (`_head_blocks`): the
blocks of x, y and the states are then the narrower group's, B and C are
still fetched by group, and a block's part of dB and dC leaves the kernel
beside the others' and is summed outside (Granite-4.0-H's ONE group of 64
heads runs as four blocks of 16: at 4,096 lanes the state and its cotangent
alone are 2 MiB each and the per-head loop is four times the text). A group
that fits a block lowers as it always did. The kernels:
`_fwd_kernel` (outputs y and the FINAL STATE), and for the backward pass
`_states_kernel` (the state's walk again, handing on each chunk's incoming
state) and `_bwd_kernel` (the chunks walked backwards, the state's
cotangent carried; its docstring has the equations). Their output
signatures are three-dim arrays, (bf16, f32), f32 alone and (bf16, bf16,
bf16, f32, f32, f32): no flash kernel's and no grouped matmul's (the
benchmark's queries tell kernels apart by them). Elsewhere the same
arithmetic in `jnp` (`_ssd_chunked`, a `lax.scan` over chunks) and XLA's
transpose of it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu._private import device_profiler

CHUNK = 128
# saved by a layer's remat policy beside the flash call's (`nemotron_h`)
RESIDUAL_NAMES = ("ssd.y",)
_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
# the widest block of heads a kernel takes at once (`_head_blocks`)
_BLOCK_LANES = 1024


def ssd_recurrence(x, delta, a, b, c):
    """The definition, token by token, float32 arithmetic -> (y [B, S, H, P]
    float32, the final state [B, H, P, N]). The tokens are walked in blocks
    under `jax.checkpoint`, so its gradient keeps a state a block and not
    one a token."""
    bsz, s, h, p = x.shape
    rep = h // b.shape[2]

    def step(state, t):
        x_t, d_t, a_t, b_t, c_t = t
        b_t, c_t = (jnp.repeat(v, rep, axis=1) for v in (b_t, c_t))
        state = state * jnp.exp(a_t)[..., None, None] \
            + (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t,
                                 precision=_HIGHEST)

    block = max(n for n in range(1, CHUNK + 1) if s % n == 0)
    by_block = lambda v: jnp.moveaxis(v.astype(jnp.float32), 1, 0).reshape(  # noqa: E731
        (s // block, block, bsz) + v.shape[2:])
    state = jnp.zeros((bsz, h, p, b.shape[-1]), jnp.float32)
    state, y = jax.lax.scan(
        jax.checkpoint(lambda state, ts: jax.lax.scan(step, state, ts)),
        state, tuple(by_block(v) for v in (x, delta, a, b, c)))
    return jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1), state


def _mm(spec, a, b, dtype):
    """einsum with operands rounded to `dtype`, float32 accumulation."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _pad_tokens(arrays, pad):
    """S padded with tokens that leave the state as it is (a = 0, Delta =
    0) and read nothing of it (C = 0)."""
    if not pad:
        return arrays
    return [jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
            for v in arrays]


def _ssd_chunked(x, delta, a, b, c, chunk=CHUNK):
    """The chunked form in `jnp` -> (y [B, S, H, P] in x.dtype, the final
    state [B, H, P, N] float32)."""
    dtype = x.dtype
    bsz, s, h, p = x.shape
    g, n_state = b.shape[2:]
    rep = h // g
    f32 = jnp.float32
    x, delta, a, b, c = _pad_tokens([x, delta, a, b, c], -s % chunk)
    n = x.shape[1] // chunk
    xs = x.astype(f32).reshape(bsz, n, chunk, g, rep, p)
    cum = jnp.cumsum(a.astype(f32).reshape(bsz, n, chunk, g, rep), axis=2)
    xd = xs * delta.astype(f32).reshape(bsz, n, chunk, g, rep)[..., None]
    bs, cs = (v.astype(f32).reshape(bsz, n, chunk, g, n_state)
              for v in (b, c))
    lower = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk))[
        None, None, :, :, None, None]
    decay = jnp.exp(jnp.where(
        lower, cum[:, :, :, None] - cum[:, :, None], -jnp.inf))
    scores = _mm("bntgk,bnsgk->bntsg", cs, bs, dtype)[..., None] * decay
    inside = _mm("bntsgr,bnsgrp->bntgrp", scores, xd, dtype)
    last = cum[:, :, -1]                                  # [B, n, G, rep]
    written = _mm("bnsgrp,bnsgk->bngrpk",
                  xd * jnp.exp(last[:, :, None] - cum)[..., None], bs, dtype)

    def step(state, t):
        c_t, cum_t, last_t, written_t = t
        read = _mm("btgk,bgrpk->btgrp", c_t, state, dtype) \
            * jnp.exp(cum_t)[..., None]
        return state * jnp.exp(last_t)[..., None, None] + written_t, read

    along = lambda v: jnp.moveaxis(v, 1, 0)  # noqa: E731
    state = jnp.zeros((bsz, g, rep, p, n_state), f32)
    state, read = jax.lax.scan(
        step, state, tuple(along(v) for v in (cs, cum, last, written)))
    y = (inside + along(read)).reshape(bsz, n * chunk, h, p)[:, :s]
    return y.astype(dtype), state.reshape(bsz, h, p, n_state)


# --------------------------------------------------------------------------
# Pallas kernels
# --------------------------------------------------------------------------

def _mm_in(dtype):
    """The kernels' matmul of two-dim operands: a's axis `dim_a` against
    b's axis `dim_b`, operands rounded to `dtype`, float32 accumulation."""
    def mm(a, b, dim_a, dim_b):
        return jax.lax.dot_general(
            a.astype(dtype), b.astype(dtype), (((dim_a,), (dim_b,)), ((), ())),
            preferred_element_type=jnp.float32)
    return mm


def _heads_a_block(heads: int, p: int) -> int:
    """Heads whose P channels fill one 128-lane block side by side (2 at P
    64): a matmul against their [Q, 128] slab costs the pass one head's
    [Q, 64] would."""
    per = max(1, _LANES // p)
    while heads % per:
        per -= 1
    return per


def _spread(piece, per: int, p: int, rows: int):
    """`piece(i)` [rows, 1] (or [1, 1]) of the block's i-th head -> [rows,
    per x p]: each head's value over its own p lanes."""
    head = jax.lax.broadcasted_iota(jnp.int32, (rows, per * p), 1) // p
    out = jnp.broadcast_to(piece(per - 1), (rows, per * p))
    for i in range(per - 1):
        out = jnp.where(head == i, piece(i), out)
    return out


def _decay_matrix(cc, cr, h: int, lower):
    """L of head h: exp(c_t - c_s) for s <= t, 0 above (masked BEFORE the
    exponential: above the diagonal the difference is positive)."""
    return jnp.exp(jnp.where(lower, cc[:, h:h + 1] - cr[h:h + 1, :],
                             -jnp.inf))


def _lower(q: int):
    return jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)


def _chunk_forward(x, dl, cc, cr, bm, cm, state, mm, p, want_y=True):
    """One chunk of one (batch, group): x [Q, heads x P], dl (Delta) and cc
    (the running log decay) [Q, heads], cr = cc^T [heads, Q], bm and cm
    [Q, N], state [N, heads x P], all float32 -> (y [Q, heads x P] or None,
    the next state). A block of heads at a time (`_heads_a_block`)."""
    q, heads = cc.shape
    per = _heads_a_block(heads, p)
    width = per * p
    lower = _lower(q)
    last = cc[q - 1:q, :]                                         # [1, heads]
    scores = mm(cm, bm, 1, 1) if want_y else None                 # [Q, Q]
    ys, states = [], []
    for first in range(0, heads, per):
        lanes = slice(first * p, first * p + width)
        spread = lambda v, rows=q: _spread(  # noqa: E731
            lambda i: v[:, first + i:first + i + 1], per, p, rows)
        xd = x[:, lanes] * spread(dl)
        old = state[:, lanes]
        if want_y:
            head = jax.lax.broadcasted_iota(jnp.int32, (q, width), 1) // p
            inside = jnp.zeros((q, width), jnp.float32)
            for i in range(per):
                part = mm(scores * _decay_matrix(cc, cr, first + i, lower),
                          xd, 1, 0)
                inside = part if per == 1 else \
                    jnp.where(head == i, part, inside)
            ys.append(inside + spread(jnp.exp(cc)) * mm(cm, old, 1, 0))
        states.append(
            old * spread(jnp.exp(last), 1)
            + mm(bm, xd * spread(jnp.exp(last - cc)), 0, 0))
    join = lambda parts: parts[0] if len(parts) == 1 \
        else jnp.concatenate(parts, axis=1)  # noqa: E731
    return (join(ys) if want_y else None), join(states)


def _loaded(*refs):
    return tuple(r[0].astype(jnp.float32) for r in refs)


def _fwd_kernel(x_ref, dl_ref, cc_ref, cr_ref, b_ref, c_ref, y_ref,
                state_ref, *, p):
    """One chunk of one (batch, group) (`_specs`); the state [1, N, heads x
    P] float32 is resident over the chunk axis and is the call's second
    output when the last chunk is done."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    y, state = _chunk_forward(
        *_loaded(x_ref, dl_ref, cc_ref, cr_ref, b_ref, c_ref), state_ref[0],
        _mm_in(x_ref.dtype), p)
    y_ref[0] = y.astype(y_ref.dtype)
    state_ref[0] = state


def _states_kernel(x_ref, dl_ref, cc_ref, cr_ref, b_ref, h_ref, state_ref, *,
                   p):
    """The backward pass's first walk, forwards: every chunk's INCOMING
    state (float32), the one thing the second walk cannot form alone. No y:
    a quarter of the forward's matmuls. `state_ref` is scratch."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    state = state_ref[...]
    h_ref[0] = state
    x, dl, cc, cr, bm = _loaded(x_ref, dl_ref, cc_ref, cr_ref, b_ref)
    state_ref[...] = _chunk_forward(
        x, dl, cc, cr, bm, None, state, _mm_in(x_ref.dtype), p,
        want_y=False)[1]


def _bwd_kernel(x_ref, dl_ref, cc_ref, cr_ref, b_ref, c_ref, dy_ref, h_ref,
                dstate_ref, dx_ref, db_ref, dc_ref, ddl_ref, dcc_ref, dcr_ref,
                dh_ref, *, p):
    """The second walk, BACKWARDS over the chunks (the grid's step j is
    chunk n - 1 - j): the cotangent of the state, `dh_ref` (scratch, float32
    [N, heads x P]), is carried from a chunk to the one before it. In the
    docstring's names, a head at a time, Xd = Delta x, M = (C B^T) * L,
    w_s = exp(c_Q - c_s), H the incoming state and H' the outgoing one:

        dM  = dY Xd^T (lower),  dXd = M^T dY + w (B dH'),  d(C B^T) += dM * L
        dC  = sum_heads [d(C B^T) B + (exp(c) dY) H],  dB likewise with C
              and + (w Xd) dH'^T
        dH  = C^T (exp(c) dY) + exp(c_Q) dH'
        dc_t = sum_s (dM * M)[t, s] - sum_s (dM * M)[s, t]
               + dY_t . (exp(c_t) C_t H^T) - w_t (B dH')_t . Xd_t
        dc_Q += sum_t w_t (B dH')_t . Xd_t + exp(c_Q) <dH', H>
        dx = Delta dXd,  dDelta = dXd . x

    The column sums of dM * M leave as rows (`dcr_ref`, [heads, Q]) and are
    taken off by the caller: no transpose in here."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(1) == 0)
    def _():
        dh_ref[...] = dstate_ref[0]

    mm = _mm_in(x_ref.dtype)
    x, dl, cc, cr, bm, cm, dy = _loaded(
        x_ref, dl_ref, cc_ref, cr_ref, b_ref, c_ref, dy_ref)
    q, heads = cc.shape
    per = _heads_a_block(heads, p)
    width = per * p
    f32 = jnp.float32
    lower = _lower(q)
    last = cc[q - 1:q, :]
    scores = mm(cm, bm, 1, 1)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    col_head = jax.lax.broadcasted_iota(jnp.int32, (q, heads), 1)
    row_head = jax.lax.broadcasted_iota(jnp.int32, (heads, q), 0)
    total = lambda v: jnp.sum(jnp.sum(v, axis=1, keepdims=True),  # noqa: E731
                              axis=0, keepdims=True)
    d_scores = jnp.zeros((q, q), f32)
    d_b = jnp.zeros(bm.shape, f32)
    d_c = jnp.zeros(cm.shape, f32)
    d_dl = jnp.zeros((q, heads), f32)
    d_cc = jnp.zeros((q, heads), f32)
    d_cr = jnp.zeros((heads, q), f32)
    for first in range(0, heads, per):
        lanes = slice(first * p, first * p + width)
        spread = lambda v, rows=q: _spread(  # noqa: E731
            lambda i: v[:, first + i:first + i + 1], per, p, rows)
        head = jax.lax.broadcasted_iota(jnp.int32, (q, width), 1) // p
        state_head = jax.lax.broadcasted_iota(
            jnp.int32, (bm.shape[1], width), 1) // p
        x_k, dy_k = x[:, lanes], dy[:, lanes]
        delta = spread(dl)
        xd = x_k * delta
        h_in, dh_out = h_ref[0, :, lanes], dh_ref[:, lanes]
        decayed, left = spread(jnp.exp(cc)), spread(jnp.exp(last - cc))
        whole = spread(jnp.exp(last), 1)                          # [1, width]
        d_xd = jnp.zeros((q, width), f32)
        for i in range(per):
            mine = head == i
            decay = _decay_matrix(cc, cr, first + i, lower)
            m = scores * decay
            d_m = mm(jnp.where(mine, dy_k, 0.0), xd, 1, 1)   # m, decay: lower
            both = d_m * m
            at = col_head == first + i
            d_cc = jnp.where(at, d_cc + jnp.sum(both, axis=1, keepdims=True),
                             d_cc)
            d_cr = jnp.where(row_head == first + i,
                             d_cr + jnp.sum(both, axis=0, keepdims=True), d_cr)
            d_scores = d_scores + d_m * decay
            part = mm(m, dy_k, 0, 0)
            d_xd = part if per == 1 else jnp.where(mine, part, d_xd)
        d_read = decayed * dy_k
        d_c = d_c + mm(d_read, h_in, 1, 1)
        read = d_read * mm(cm, h_in, 1, 0)
        xw = xd * left
        d_xw = mm(bm, dh_out, 1, 0)
        d_b = d_b + mm(xw, dh_out, 1, 1)
        d_xd = d_xd + left * d_xw
        wrote = d_xw * xw
        kept = dh_out * h_in * whole                              # [N, width]
        dh_ref[:, lanes] = mm(cm, d_read, 0, 0) + whole * dh_out
        dx_ref[0, :, lanes] = (delta * d_xd).astype(dx_ref.dtype)
        by_x = d_xd * x_k
        for i in range(per):
            mine = head == i
            of = lambda v: jnp.sum(jnp.where(mine, v, 0.0), axis=1,  # noqa: E731
                                   keepdims=True)
            wrote_h = of(wrote)
            d_last = total(wrote_h) + total(
                jnp.where(state_head == i, kept, 0.0))
            at = col_head == first + i
            d_cc = jnp.where(at, d_cc + of(read) - wrote_h
                             + jnp.where(is_last, d_last, 0.0), d_cc)
            d_dl = jnp.where(at, of(by_x), d_dl)
    db_ref[0] = (d_b + mm(d_scores, cm, 0, 0)).astype(db_ref.dtype)
    dc_ref[0] = (d_c + mm(d_scores, bm, 1, 0)).astype(dc_ref.dtype)
    ddl_ref[0] = d_dl
    dcc_ref[0] = d_cc
    dcr_ref[0] = d_cr


def _head_blocks(heads: int, p: int) -> int:
    """The blocks a group of `heads` heads is walked in: 1 where its heads
    x P lanes fit `_BLOCK_LANES`, else the fewest equal blocks that do."""
    blocks = 1
    while heads % blocks or heads // blocks * p > _BLOCK_LANES:
        blocks += 1
        if blocks > heads:
            raise ValueError(f"a head of {p} lanes is past {_BLOCK_LANES}")
    return blocks


def _flat(x, delta, a, b, c, *more, chunk, blocks):
    """-> (the kernels' operands, padded to whole chunks: x and `more`
    [B, S, H x P], Delta and the running log decay [B x G x blocks, S,
    H / G / blocks] (a grid row's heads), the latter's transpose, b and c
    [B, S, G x N]; the chunks' number)."""
    bsz, s, h, p = x.shape
    g = b.shape[2] * blocks
    wide = _pad_tokens([x, *more], -s % chunk)
    delta, a, b, c = _pad_tokens([delta, a, b, c], -s % chunk)
    s = wide[0].shape[1]
    n = s // chunk
    by_group = lambda v: jnp.swapaxes(  # noqa: E731
        v.astype(jnp.float32).reshape(bsz, s, g, h // g), 1, 2).reshape(
            bsz * g, s, h // g)
    cum = by_group(jnp.cumsum(
        a.astype(jnp.float32).reshape(bsz, n, chunk, h), axis=2))
    lanes = lambda v: v.reshape(bsz, s, -1)  # noqa: E731
    x, *more = (lanes(v) for v in wide)
    return (x, by_group(delta), cum, jnp.swapaxes(cum, 1, 2), lanes(b),
            lanes(c), *more), n


def _specs(g: int, n: int, heads: int, p: int, n_state: int, chunk: int,
           blocks: int = 1, reverse=False):
    """Block specs of a grid (batch x group x blocks, chunks), `heads` the
    heads of ONE block: `lanes(width)` a [B, S, rows a batch x width]
    array's chunk of this grid row, `group(width)` a [B, S, G x width]
    array's chunk of this row's GROUP (B and C), `cols` / `rows` the
    [B x G x blocks, S, heads] scalars' and their transpose's, `held` this
    row's block of the state [B x G, N, blocks x heads x P], which stays
    over the chunk axis, `walked` a chunk's block of the [B x G, chunks x N,
    blocks x heads x P] states. With one block a group the index maps are
    the ones there always were (chosen here, not traced)."""
    from jax.experimental import pallas as pl

    at = (lambda j: n - 1 - j) if reverse else (lambda j: j)
    per = g * blocks  # grid rows a batch
    if blocks == 1:
        group = lambda i: i % g  # noqa: E731
        state = lambda i, j: (i, j, 0)  # noqa: E731
    else:
        group = lambda i: i % per // blocks  # noqa: E731
        state = lambda i, j: (i // blocks, j, i % blocks)  # noqa: E731
    return dict(
        lanes=lambda width: pl.BlockSpec(
            (1, chunk, width), lambda i, j: (i // per, at(j), i % per)),
        group=lambda width: pl.BlockSpec(
            (1, chunk, width), lambda i, j: (i // per, at(j), group(i))),
        cols=pl.BlockSpec((1, chunk, heads), lambda i, j: (i, at(j), 0)),
        rows=pl.BlockSpec((1, heads, chunk), lambda i, j: (i, 0, at(j))),
        held=pl.BlockSpec((1, n_state, heads * p),
                          lambda i, j: state(i, 0)),
        walked=pl.BlockSpec((1, n_state, heads * p),
                            lambda i, j: state(i, at(j))))


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _count_kernels(n: int, heads: int, p: int):
    """Per lowering: the kernels of a call, and of them those lowered for
    a group of more than `_BLOCK_LANES` lanes (walked in head blocks)."""
    device_profiler.count("ssd.kernels", n)
    device_profiler.count("ssd.kernels_wide_group",
                          n * (heads * p > _BLOCK_LANES))


def _state_out(state, bsz, g, heads, p):
    """The kernels' [B x G, N, heads x P] -> [B, H, P, N]."""
    n_state = state.shape[1]
    return jnp.transpose(state.reshape(bsz, g, n_state, heads, p),
                         (0, 1, 3, 4, 2)).reshape(bsz, g * heads, p, n_state)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_fwd_pallas(x, delta, a, b, c, chunk=CHUNK, interpret=False):
    from jax.experimental import pallas as pl

    bsz, s, h, p = x.shape
    g, n_state = b.shape[2:]
    heads = h // g
    blocks = _head_blocks(heads, p)
    block = heads // blocks
    _count_kernels(1, heads, p)
    args, n = _flat(x, delta, a, b, c, chunk=chunk, blocks=blocks)
    sp = _specs(g, n, block, p, n_state, chunk, blocks)
    y, state = pl.pallas_call(
        partial(_fwd_kernel, p=p),
        grid=(bsz * g * blocks, n),
        in_specs=[sp["lanes"](block * p), sp["cols"], sp["cols"], sp["rows"],
                  sp["group"](n_state), sp["group"](n_state)],
        out_specs=[sp["lanes"](block * p), sp["held"]],
        out_shape=[jax.ShapeDtypeStruct(args[0].shape, x.dtype),
                   jax.ShapeDtypeStruct((bsz * g, n_state, heads * p),
                                        jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
    )(*args)
    return (y[:, :s].reshape(x.shape), _state_out(state, bsz, g, heads, p))


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_bwd_pallas(x, delta, a, b, c, dy, dstate, chunk=CHUNK,
                    interpret=False):
    """The five gradients from TWO calls: `_states_kernel` forwards, then
    `_bwd_kernel` backwards. What the second hands back of the running sums
    (c as columns, less c as rows) becomes a's gradient here: a reversed
    cumulative sum inside each chunk. A group walked in head blocks leaves
    a part of dB and dC a block, summed here in float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bsz, s, h, p = x.shape
    g, n_state = b.shape[2:]
    heads = h // g
    blocks = _head_blocks(heads, p)
    block = heads // blocks
    _count_kernels(2, heads, p)
    (x_, dl_, cc_, cr_, b_, c_, dy_), n = _flat(
        x, delta, a, b, c, dy, chunk=chunk, blocks=blocks)
    f32 = jnp.float32
    shaped = jax.ShapeDtypeStruct
    rows = bsz * g * blocks
    sp = _specs(g, n, block, p, n_state, chunk, blocks)
    states = pl.pallas_call(
        partial(_states_kernel, p=p),
        grid=(rows, n),
        in_specs=[sp["lanes"](block * p), sp["cols"], sp["cols"], sp["rows"],
                  sp["group"](n_state)],
        out_specs=sp["walked"],
        out_shape=shaped((bsz * g, n * n_state, heads * p), f32),
        scratch_shapes=[pltpu.VMEM((n_state, block * p), f32)],
        compiler_params=_params(),
        interpret=interpret,
    )(x_, dl_, cc_, cr_, b_)
    dstate = jnp.transpose(
        dstate.astype(f32).reshape(bsz, g, heads, p, n_state),
        (0, 1, 4, 2, 3)).reshape(bsz * g, n_state, heads * p)
    sp = _specs(g, n, block, p, n_state, chunk, blocks, reverse=True)
    per_head = shaped((rows, n * chunk, block), f32)
    by_block = lambda v, like: shaped(  # noqa: E731
        v.shape[:2] + (blocks * v.shape[2],), like.dtype)
    dx, db, dc, ddl, dcc, dcr = pl.pallas_call(
        partial(_bwd_kernel, p=p),
        grid=(rows, n),
        in_specs=[sp["lanes"](block * p), sp["cols"], sp["cols"], sp["rows"],
                  sp["group"](n_state), sp["group"](n_state),
                  sp["lanes"](block * p), sp["walked"], sp["held"]],
        out_specs=[sp["lanes"](block * p), sp["lanes"](n_state),
                   sp["lanes"](n_state), sp["cols"], sp["cols"], sp["rows"]],
        out_shape=[shaped(x_.shape, x.dtype), by_block(b_, b), by_block(c_, c),
                   per_head, per_head, shaped((rows, block, n * chunk), f32)],
        scratch_shapes=[pltpu.VMEM((n_state, block * p), f32)],
        compiler_params=_params(),
        interpret=interpret,
    )(x_, dl_, cc_, cr_, b_, c_, dy_, states, dstate)
    by_token = lambda v: jnp.swapaxes(  # noqa: E731
        v.reshape(bsz, g * blocks, n * chunk, block), 1, 2).reshape(
            bsz, n * chunk, h)
    d_cum = by_token(dcc - jnp.swapaxes(dcr, 1, 2)).reshape(bsz, n, chunk, h)
    d_a = jnp.flip(jnp.cumsum(jnp.flip(d_cum, 2), axis=2), 2).reshape(
        bsz, n * chunk, h)
    if blocks > 1:
        db, dc = (jnp.sum(v.reshape(bsz, n * chunk, g, blocks, n_state),
                          axis=3, dtype=f32).astype(v.dtype)
                  for v in (db, dc))
    return (dx[:, :s].reshape(x.shape), by_token(ddl)[:, :s].astype(delta.dtype),
            d_a[:, :s].astype(a.dtype), db[:, :s].reshape(b.shape),
            dc[:, :s].reshape(c.shape))


# --------------------------------------------------------------------------
# the call
# --------------------------------------------------------------------------

def _forward(x, delta, a, b, c, chunk, use_pallas, interpret):
    device_profiler.count("ssd.calls", 1)  # per lowering
    device_profiler.count("ssd.chunks", -(-x.shape[1] // chunk))
    if use_pallas or interpret:
        return _ssd_fwd_pallas(x, delta, a, b, c, chunk=chunk,
                               interpret=interpret)
    return _ssd_chunked(x, delta, a, b, c, chunk)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ssd(x, delta, a, b, c, use_pallas, interpret, chunk=CHUNK):
    return _forward(x, delta, a, b, c, chunk, use_pallas, interpret)


def _ssd_fwd_rule(x, delta, a, b, c, use_pallas, interpret, chunk):
    y, state = _forward(x, delta, a, b, c, chunk, use_pallas, interpret)
    return (checkpoint_name(y, RESIDUAL_NAMES[0]), state), (x, delta, a, b, c)


def _ssd_bwd_rule(use_pallas, interpret, chunk, res, cotangents):
    """On a TPU the two backward kernels; elsewhere XLA's transpose of the
    chunked arithmetic."""
    if use_pallas or interpret:
        return _ssd_bwd_pallas(*res, *cotangents, chunk=chunk,
                               interpret=interpret)
    return jax.vjp(partial(_ssd_chunked, chunk=chunk), *res)[1](cotangents)


_ssd.defvjp(_ssd_fwd_rule, _ssd_bwd_rule)


def ssd_scan(x, delta, a, b, c, *, chunk=CHUNK, use_pallas=None,
             interpret=False):
    """x [B, S, H, P], delta (> 0) and a (<= 0, the log decay a step)
    [B, S, H] float32, b and c [B, S, G, N] -> (y [B, S, H, P] in x.dtype,
    the final state [B, H, P, N] float32), walked `chunk` tokens at a time.
    `use_pallas=None`: the Pallas kernels on a TPU, `jnp` elsewhere
    (`interpret=True` runs the kernels in the Pallas interpreter)."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu" and not interpret
    return _ssd(x, delta, a, b, c, bool(use_pallas), bool(interpret),
                int(chunk))


def ssd(x, dt, a_log, b, c, d_skip, dt_bias, **how):
    """The layer's scan from its parameters: Delta = softplus(dt + dt_bias),
    a = -exp(A_log) Delta, y_t = H_t C_t + D x_t. dt [B, S, H]; a_log,
    d_skip, dt_bias [H] -> y [B, S, H, P] in x.dtype."""
    f32 = jnp.float32
    delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    y, _ = ssd_scan(x, delta, -jnp.exp(a_log.astype(f32)) * delta, b, c,
                    **how)
    return (y.astype(f32) + d_skip.astype(f32)[:, None]
            * x.astype(f32)).astype(x.dtype)
