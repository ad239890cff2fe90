"""The KDA kernels' q, k, v and g made from their projections by one Pallas
call each way (`prep` for q, k and v together, `gate` for g).

`mixers.kda_sublayer` has the mathematics (`kda_operands`, plain `jnp`: the
definition, and what runs wherever `fused` says no): a projection's output x
[B, S, H, D] goes through a causal depthwise conv of K taps over time, SiLU,
for q and k an l2-norm of the head (q times D^-1/2 besides), is rounded to
the model's dtype and handed to `ops/kda.kda` heads first, [B, H, S, D]. In
XLA that is a handful of passes over each tensor each way and two column
reduces over the tokens for the taps' gradient. Here it is

    `prep` forward   reads x_q, x_k, x_v, the taps       writes q, k, v
    `prep` backward  reads dq, dk, dv, x_q, x_k, x_v     writes dx_q, dx_k,
                                                         dx_v, d taps

one read and one write a tensor. x is taken as [B, S, H D] (the projection's
own rows) in blocks (1, tile, D) picked by (batch, token tile, head), q as
[B, H, S, D] in blocks (1, 1, tile, D): where D is a lane tile's multiple the
move to heads first is the OUTPUT'S INDEX MAP, no transpose in the kernel and
no pass of its own. The conv's K - 1 rows before a tile come as a second
block of the same array, the `HALO` rows that end where the tile starts
(zeros before token 0).

The backward call walks a (batch, head)'s token tiles from the LAST to the
first (that grid axis is `arbitrary`): it recomputes the conv and SiLU of the
tile from the same x the forward read (no residual of its own: under a remat
policy that keeps the projections' outputs x is kept as before, under one that
does not it is rerun as before), applies the norm's and SiLU's derivatives,
and the conv's transpose needs d u of the K - 1 rows AFTER the tile, which are
the first rows of the tile it did one step earlier: they wait in a VMEM
scratch. The taps' gradient is summed over the tiles in a float32 block that
stays resident over the token axis, a (batch, head) a block, and the batches
are added up outside.

`gate` is the decay's elementwise side through the same blocks and index
maps (`mixers.kda_decay`: g = -exp(A_log) softplus(a + dt_bias), or the
bounded bound x sigmoid(exp(A_log) (a + dt_bias)), float32 [B, H, S, D], twice
q's bytes): forward a read of a and a write of g; backward a read of dg and a,
a write of da, and the two sums over the tokens that `dt_bias` and `A_log`
need in a [2, D] block resident over the token axis. beta, the output gate
and the head norm stay XLA's.

The arithmetic is the `jnp` lines': float32 inside, rounded once where they
round (the lines round each tap's term of dx to bf16 and add the four: a call
rounds the sum). Outputs: `prep` three bf16 [B, H, S, D] forward, three bf16
[B, S, H D] and a float32 [B, 3 K, H D] backward; `gate` one float32 [B, H, S,
D] forward, a bf16 [B, S, H D] and a float32 [B, H, 2, D] backward: no other
kernel's signature (the benchmark's queries tell Pallas events apart by their
outputs' shapes; these calls' own name is their scope's, `kda.prep`).

NO CALL STATES A VMEM LIMIT (`ops/stream_mix.py` has why: beside a share's
routed block's backward pass every call that stated one hung the v5e): a tile
of `TOKEN_TILE` tokens of one head fits the 16 MiB a call gets unasked with
room to spare, and `fused` refuses heads so wide that it would not.

`fused(shape, taps, mesh)` says whether a layer takes the calls: what the code
can observe (the backend, the operands' shapes, the mesh's size), no option.
"""

from __future__ import annotations

import operator
from functools import partial, reduce

import jax
import jax.numpy as jnp

# tokens a grid step; the rows of the block that holds a tile's K - 1 rows
# before it (a bf16 tile's sublanes); the rows d u waits in for the tile before
TOKEN_TILE = 1024
HALO = 16
CARRY = 8
# the tests' seam: run the calls in the Pallas interpreter where no TPU is
INTERPRET = False
_LANES = 128
# the widest head a call takes: at 256 (as at 2,048 tokens of 128) the v5e's
# compiler refuses `prep`'s backward call the 16 MiB a call gets unasked
_MAX_HEAD = 128
_EPS = 1e-6
_F32 = jnp.float32


def fused(shape, taps, mesh=None) -> bool:
    """Whether q, k, v and g of a layer whose projections are `shape` = (B, S,
    H, D) and whose taps are shaped like `taps` [K, H, D] take the Pallas
    calls: on a TPU (or under `INTERPRET`), D a multiple of 128 and no wider
    than `_MAX_HEAD`, S a multiple of the token tile, the taps no more than
    the carry's rows + 1, one device."""
    _, s, _, d = shape
    return bool((INTERPRET or jax.default_backend() == "tpu")
                and d % _LANES == 0 and d <= _MAX_HEAD
                and s % TOKEN_TILE == 0 and 1 <= taps.shape[0] <= CARRY + 1
                and (mesh is None or mesh.size == 1))


# --------------------------------------------------------------------------
# inside the kernels
# --------------------------------------------------------------------------

def _sum(terms):
    """The terms added up in order, without `sum`'s leading 0 + (an add a
    register of the vector unit's, which no pass folds away)."""
    return reduce(operator.add, terms)


def _conv_silu(x_ref, halo_ref, rows_ref, taps, first):
    """A tile's x (tile, D) and the `HALO` rows before it (zeros where
    `first`), taps (K, D) float32 -> (the K shifted copies of x the conv
    reads, u = the conv, sigmoid(u)), float32: `mixers._short_conv`'s sum,
    tap 0 on the oldest row. The rows are laid out in `rows_ref` (HALO +
    tile, D) float32 and read back K times at a row's offset: a load at any
    sublane is the load unit's work, where a shifted slice of a value is
    two rotations and a select of the vector unit's a register."""
    k = taps.shape[0]
    tile = x_ref.shape[1]
    x = x_ref[0].astype(_F32)
    rows_ref[0:HALO, :] = jnp.where(first, 0.0, halo_ref[0].astype(_F32))
    rows_ref[HALO:HALO + tile, :] = x
    at = HALO - (k - 1)
    shifted = [rows_ref[at + j:at + j + tile, :] for j in range(k - 1)] + [x]
    u = _sum(shifted[j] * taps[j:j + 1] for j in range(k))
    return shifted, u, jax.nn.sigmoid(u)


def _operands_kernel(*refs, scale):
    from jax.experimental import pallas as pl

    xs, halos, taps_ref, outs = refs[0:3], refs[3:6], refs[6], refs[7:10]
    rows_ref = refs[10]
    first = pl.program_id(2) == 0
    for m, (x_ref, halo_ref, out_ref) in enumerate(zip(xs, halos, outs)):
        _, u, sig = _conv_silu(x_ref, halo_ref, rows_ref, taps_ref[m], first)
        y = u * sig
        if m < 2:   # q and k: the head's l2-norm
            y = y * jax.lax.rsqrt(
                jnp.sum(y * y, axis=-1, keepdims=True) + _EPS)
        if m == 0:
            y = y * scale
        out_ref[0, 0] = y.astype(out_ref.dtype)


def _cotangents_kernel(*refs, scale):
    """A grid step is the tile `tiles - 1 - program_id(2)`: du's first rows
    wait in `carry_ref` for the tile before, which is the NEXT step."""
    from jax.experimental import pallas as pl

    douts, xs, halos, taps_ref = refs[0:3], refs[3:6], refs[6:9], refs[9]
    dxs, dtaps_ref = refs[10:13], refs[13]
    rows_ref, du_ref, carry_ref = refs[14:17]
    step = pl.program_id(2)
    first = step == pl.num_programs(2) - 1   # the tile that starts at token 0
    k = taps_ref.shape[1]
    tile = xs[0].shape[1]

    @pl.when(step == 0)
    def _():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)
        carry_ref[...] = jnp.zeros_like(carry_ref)

    for m, (dout_ref, x_ref, halo_ref, dx_ref) in enumerate(
            zip(douts, xs, halos, dxs)):
        taps = taps_ref[m]
        shifted, u, sig = _conv_silu(x_ref, halo_ref, rows_ref, taps, first)
        dy = dout_ref[0, 0].astype(_F32)
        if m < 2:
            # z = c y r, r = (sum y^2 + eps)^-1/2:
            # dy = c r (dz - y r^2 <dz, y>)
            y = u * sig
            r = jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + _EPS)
            dot = jnp.sum(dy * y, axis=-1, keepdims=True)
            dy = (dy - y * (r * r * dot)) * (r * scale if m == 0 else r)
        # SiLU's slope, sig (1 + u (1 - sig)), with y = u sig
        du = dy * (sig + (u * sig) * (1.0 - sig))
        for j in range(k):
            row = pl.ds(m * k + j, 1)
            dtaps_ref[0, row, :] = dtaps_ref[0, row, :] + jnp.sum(
                du * shifted[j], axis=0, keepdims=True)
        # the conv's transpose: dx_s = sum_j taps[j] du_{s + K - 1 - j}, du
        # laid out over the rows that wait from the tile after
        du_ref[0:tile, :] = du
        du_ref[tile:tile + CARRY, :] = carry_ref[m]
        dx = _sum([du_ref[k - 1 - j:k - 1 - j + tile, :] * taps[j:j + 1]
                   for j in range(k - 1)] + [du * taps[k - 1:k]])
        carry_ref[m] = du[0:CARRY]
        dx_ref[0] = dx.astype(dx_ref.dtype)


def _decay(a, rate, bound):
    """a = the gate's pre-activation with its bias, `rate` = exp(A_log) a
    channel, float32 -> (g, dg/da, dg/d rate): `mixers.kda_sublayer`'s two
    forms, the softplus gate where `bound` is None and the bounded sigmoid
    `bound` x sigmoid(rate a) otherwise."""
    if bound is None:
        soft = jax.nn.softplus(a)
        return -rate * soft, -rate * jax.nn.sigmoid(a), -soft
    sig = jax.nn.sigmoid(rate * a)
    slope = bound * sig * (1.0 - sig)
    return bound * sig, slope * rate, slope * a


def _gate_kernel(a_ref, coef_ref, g_ref, *, bound):
    coef = coef_ref[...]
    g_ref[0, 0] = _decay(a_ref[0].astype(_F32) + coef[0:1], coef[1:2],
                         bound)[0]


def _gate_cotangent_kernel(dg_ref, a_ref, coef_ref, da_ref, dcoef_ref, *,
                           bound):
    """-> da, and in `dcoef_ref` (resident over the token axis) the sums over
    the tokens of da (the bias's gradient) and of dg x dg/d rate."""
    from jax.experimental import pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        dcoef_ref[...] = jnp.zeros_like(dcoef_ref)

    coef = coef_ref[...]
    _, by_a, by_rate = _decay(a_ref[0].astype(_F32) + coef[0:1], coef[1:2],
                              bound)
    dg = dg_ref[0, 0]
    da = dg * by_a
    da_ref[0] = da.astype(da_ref.dtype)
    dcoef_ref[0, 0] = dcoef_ref[0, 0] + jnp.concatenate(
        [jnp.sum(da, axis=0, keepdims=True),
         jnp.sum(dg * by_rate, axis=0, keepdims=True)], axis=0)


# --------------------------------------------------------------------------
# the calls
# --------------------------------------------------------------------------

def _call(kernel, grid, ins, outs, scratch, interpret):
    """`pl.pallas_call` under `jax.named_scope("kda.prep")` as the innermost
    name (the compiler names the call's instruction, and so its events in a
    trace, by it: `%kda.prep.3`): `ins` / `outs` are (array or its shape and
    dtype, block shape, index map), `scratch` (shape, dtype). The token
    axis, the grid's last, is walked in order."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    call = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[pl.BlockSpec(b, m) for _, b, m in ins],
        out_specs=[pl.BlockSpec(b, m) for _, b, m in outs],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a, _, _ in outs],
        scratch_shapes=[pltpu.VMEM(s, t) for s, t in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret)
    with jax.named_scope("kda.prep"):
        return call(*[a for a, _, _ in ins])


_shaped = jax.ShapeDtypeStruct


def _blocks(x, d, tile, tile_of):
    """x [B, S, H D] -> (grid, x's block, its halo's, a heads-first block):
    `tile_of(t)` is the tile a grid step's last index stands for."""
    b, s, hd = x.shape
    per = tile // HALO
    return ((b, hd // d, s // tile),
            ((1, tile, d), lambda i, h, t: (i, tile_of(t), h)),
            ((1, HALO, d),
             lambda i, h, t: (i, jnp.maximum(tile_of(t) * per - 1, 0), h)),
            ((1, 1, tile, d), lambda i, h, t: (i, h, tile_of(t), 0)))


def _a_heads(a, d):
    """A small array [..., H D] -> its (array, block, index map): a head's
    channels, whole in every other dim."""
    lead = (0,) * (a.ndim - 1)
    return (a, a.shape[:-1] + (d,), lambda i, h, t: lead + (h,))


@partial(jax.jit, static_argnames=("d", "tile", "dtype", "interpret"))
def _prep_fwd(xq, xk, xv, taps, d, tile, dtype, interpret=False):
    """x [B, S, H D] each, taps [3, K, H D] float32 -> q, k, v [B, H, S, D]
    in `dtype`."""
    b, s, hd = xq.shape
    grid, rows, halo, heads_first = _blocks(xq, d, tile, lambda t: t)
    out = (_shaped((b, hd // d, s, d), dtype),) + heads_first
    return _call(
        partial(_operands_kernel, scale=d ** -0.5), grid,
        [(x,) + rows for x in (xq, xk, xv)]
        + [(x,) + halo for x in (xq, xk, xv)] + [_a_heads(taps, d)],
        [out] * 3, [((HALO + tile, d), _F32)], interpret)


@partial(jax.jit, static_argnames=("d", "tile", "interpret"))
def _prep_bwd(dq, dk, dv, xq, xk, xv, taps, d, tile, interpret=False):
    """-> (dx_q, dx_k, dx_v [B, S, H D], d taps [B, 3 K, H D] float32, a
    batch's apart)."""
    b, s, hd = xq.shape
    k = taps.shape[1]
    tiles = s // tile
    grid, rows, halo, heads_first = _blocks(
        xq, d, tile, lambda t: tiles - 1 - t)
    return _call(
        partial(_cotangents_kernel, scale=d ** -0.5), grid,
        [(g,) + heads_first for g in (dq, dk, dv)]
        + [(x,) + rows for x in (xq, xk, xv)]
        + [(x,) + halo for x in (xq, xk, xv)] + [_a_heads(taps, d)],
        [(_shaped(x.shape, x.dtype),) + rows for x in (xq, xk, xv)]
        + [(_shaped((b, 3 * k, hd), _F32), (1, 3 * k, d),
            lambda i, h, t: (i, 0, h))],
        [((HALO + tile, d), _F32), ((tile + CARRY, d), _F32),
         ((3, CARRY, d), _F32)], interpret)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _prep(xq, xk, xv, taps, d, tile, dtype, interpret):
    return tuple(_prep_fwd(xq, xk, xv, taps, d, tile, dtype, interpret))


def _prep_vjp_fwd(xq, xk, xv, taps, *how):
    return _prep(xq, xk, xv, taps, *how), (xq, xk, xv, taps)


def _prep_vjp_bwd(d, tile, dtype, interpret, saved, cotangents):
    xq, xk, xv, taps = saved
    *dxs, dtaps = _prep_bwd(*cotangents, xq, xk, xv, taps, d, tile, interpret)
    return (*dxs, jnp.sum(dtaps, axis=0).reshape(taps.shape))


_prep.defvjp(_prep_vjp_fwd, _prep_vjp_bwd)


@partial(jax.jit, static_argnames=("d", "tile", "bound", "interpret"))
def _gate_fwd(a, coef, d, tile, bound, interpret=False):
    """a [B, S, H D], coef [2, H D] float32 (the bias; exp(A_log) a channel)
    -> g [B, H, S, D] float32."""
    b, s, hd = a.shape
    grid, rows, _, heads_first = _blocks(a, d, tile, lambda t: t)
    (g,) = _call(
        partial(_gate_kernel, bound=bound), grid,
        [(a,) + rows, _a_heads(coef, d)],
        [(_shaped((b, hd // d, s, d), _F32),) + heads_first], [], interpret)
    return g


@partial(jax.jit, static_argnames=("d", "tile", "bound", "interpret"))
def _gate_bwd(dg, a, coef, d, tile, bound, interpret=False):
    """-> (da [B, S, H D], [B, H, 2, D] float32: a batch's sums over the
    tokens of da and of dg x dg/d rate)."""
    b, s, hd = a.shape
    grid, rows, _, heads_first = _blocks(a, d, tile, lambda t: t)
    return _call(
        partial(_gate_cotangent_kernel, bound=bound), grid,
        [(dg,) + heads_first, (a,) + rows, _a_heads(coef, d)],
        [(_shaped(a.shape, a.dtype),) + rows,
         (_shaped((b, hd // d, 2, d), _F32), (1, 1, 2, d),
          lambda i, h, t: (i, h, 0, 0))], [], interpret)


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _gate(a, coef, d, tile, bound, interpret):
    return _gate_fwd(a, coef, d, tile, bound, interpret)


def _gate_vjp_fwd(a, coef, *how):
    return _gate(a, coef, *how), (a, coef)


def _gate_vjp_bwd(d, tile, bound, interpret, saved, dg):
    a, coef = saved
    da, dcoef = _gate_bwd(dg, a, coef, d, tile, bound, interpret)
    # [B, H, 2, D] -> [2, H D]
    return da, jnp.sum(dcoef, axis=0).swapaxes(0, 1).reshape(coef.shape)


_gate.defvjp(_gate_vjp_fwd, _gate_vjp_bwd)


def gate(a, dt_bias, a_log, bound):
    """The decay gate's projection as rows a [B, S, H D], `dt_bias` [H, D],
    `a_log` [H], `bound` (the config's `kda_lower_bound`: None for the
    softplus gate) -> g [B, H, S, D] float32 as `mixers.kda_decay` makes it
    of [B, S, H, D]. The caller has asked `fused`."""
    h, d = dt_bias.shape
    rate = jnp.exp(a_log.astype(_F32))[:, None]
    coef = jnp.stack([dt_bias.astype(_F32).reshape(h * d),
                      jnp.broadcast_to(rate, (h, d)).reshape(h * d)])
    return _gate(a, coef, d, TOKEN_TILE,
                 None if bound is None else float(bound), INTERPRET)


def prep(xs, taps, dtype):
    """xs: the q, k and v projections' outputs as rows [B, S, H D] each; taps:
    their convs' [K, H, D] each -> q, k, v [B, H, S, D] in `dtype`, as
    `mixers.kda_operands` makes them of [B, S, H, D]. The caller has asked
    `fused`."""
    k, h, d = taps[0].shape
    stacked = jnp.stack([t.astype(_F32).reshape(k, h * d) for t in taps])
    return _prep(*xs, stacked, d, TOKEN_TILE, jnp.dtype(dtype), INTERPRET)
