"""One connection of a residual path of several streams as four Pallas calls.

`models/streams.py` has the mathematics (mHC: maps H_pre, H_post, H_res of a
token's n x D values, H_res Sinkhorn-normalised; h = sum_i H_pre[i] X[i];
X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y). In plain `jnp` a connection is
a dozen passes over the streams each way. Here it is two calls each way, the
sublayer F between them:

    `pre_mix`  forward   reads X                        writes h, the maps
    `post_mix` forward   reads X, y, the maps           writes X'
    `post_mix` backward  reads dX', X, y, the maps      writes dy, d(maps)
    `pre_mix`  backward  reads dX', X, dh, d(maps), a   writes dX, d phi, du

X is [n, tokens, D], h and y [tokens, D] (three and two dims: no flash
kernel's outputs, which the benchmark's queries tell apart by theirs). The
maps travel between the calls as ROWS, [tiles, R, tile] float32, a token
tile's block whole and its tokens minor: a group of 8 rows (a float32 tile's
sublanes) for H_pre, one for H_post and one for each row of H_res, the first
n rows of a group live, R = 8 (n + 2). phi is laid the same way, [R, n D]
(`phi_rows`), so vec(X) . phi is one MXU product a stream and D chunk with
the tokens leaving minor, and alpha and b are two columns of `coef` [R, 2];
`to_rows` / `from_rows` / `maps_of` move between [n + n + n^2, ...] and the
rows, in `jnp`, so JAX transposes them itself.

Each call is a `jax.custom_vjp`, and the two are joined by ONE edge that is
not a cotangent's: `pre_mix` hands X on to `post_mix` (its third result, X
itself), and `post_mix`'s backward hands back on that edge dX' AS IT IS, not
H_res^T dX'. `pre_mix`'s backward does that product where it writes dX: M^T
dX' + H_pre dh + the maps' path (the norm's, and ds . phi on the MXU), so dX
is written once and no [n, tokens, D] array is summed in XLA. Nothing else
may read that third result (`streams.connect` is its only caller).

The arithmetic is `streams.maps`': bf16 X against bf16 phi with float32 sums,
times 1 / r afterwards; the maps, every Sinkhorn iteration, `eps` in every
denominator, the clip and exact division in float32; sums over streams in
float32 rounded once. The backward call recomputes the maps and Sinkhorn's
iterates in VMEM from a = vec(X) . phi / r (saved: R x tokens float32, 1.5
MB a connection, alive within a layer's backward only) and takes their
transpose by `jax.vjp` of the same few lines the forward runs. Where float32
values cross the MXU they cross it exactly: a row of maps becomes a column
(tokens on sublanes, as X has them) as identity x rows^T at `HIGHEST`, a sum
over lanes comes back as a row as one-hot x partials^T at `HIGHEST`, and ds,
float32, meets bf16 X and phi as a high and a low bf16 half stacked in one
product (K = 2 R <= 128: no more MXU passes than one half).

Inside a call a tile of `TOKEN_TILE` tokens is resident with all of D; the
MXU products run on the tile, the vector unit's passes on groups of
`ROW_GROUP` rows and `_lane_chunk` lanes so that a group's values stay in
registers. The backward `hc.pre` call's grid is token tiles x STREAMS: a
tile's first step does what its streams share (the maps' transpose, d phi),
every step writes one stream's dX, so one stream's block and one ds . phi
product are resident and not n.

NO CALL STATES A VMEM LIMIT, and the tile is sized for that: on the v5e a
call gets 16 MiB where it states nothing, and the largest, `hc.pre`'s
backward, keeps `_resident(n, D)` bytes there (16 MiB at n 4, D 4,096). In a
train step that also holds a share's routed block (`parallel/moe`) in its
backward pass, every Pallas call that stated a limit hung the chip: these
at 30 to 78 MiB, and an EMPTY call stating 64, 30 or 20 MiB in the `jnp`
path's program, where the same empty call stating nothing ran (my chip
runs, PERF.md section 6, PR 62). Why is not known; until it is, a call
beside such a block fits the default or is not made.

`fused(X, mesh)` says whether a connection takes the calls: what the code can
observe (the backend, X's dtype and shape, the mesh's size), no option.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

GROUP = 8
# tokens a grid step: the largest at which every call's blocks fit the VMEM a
# call gets WITHOUT stating a limit (16 MiB on the v5e; `fused` has why);
# rows a pass of the vector unit (a bf16 tile's sublanes)
TOKEN_TILE = 64
ROW_GROUP = 16
# the tests' seam: run the calls in the Pallas interpreter where no TPU is
INTERPRET = False
_LANES = 128
# what a call that states no limit gets of the v5e's VMEM
_VMEM = 16 << 20
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))
_F32 = jnp.float32


class Spec(NamedTuple):
    """What a connection's kernels are compiled for, from the config."""
    n: int
    iters: int
    eps: float
    lo: float
    hi: float
    norm_eps: float


def spec_of(config, n: int) -> Spec:
    c = config
    return Spec(n, c.hc_sinkhorn_iters, float(c.hc_eps),
                float(c.h_res_clamp_min), float(c.h_res_clamp_max),
                float(c.norm_eps))


def rows_of(n: int) -> int:
    return GROUP * (n + 2)


def _resident(n: int, d: int) -> int:
    """Bytes the largest call, `hc.pre`'s backward, keeps in VMEM: its moving
    blocks twice (dX' and X [n, tile, D], dh, one stream's dX and its rows
    of phi, bf16), d phi [R, n D] and ds . phi [tile, D] in float32. The
    v5e's compiler places the call at n 4, D 4,096, where this says 16 MiB,
    and refuses D 4,608."""
    tile, rows = TOKEN_TILE, rows_of(n)
    phi2 = -(-2 * rows // _LANES) * _LANES
    return 4 * d * (2 * n * tile + 2 * tile + phi2) + 4 * d * (rows * n + tile)


def fused(X, mesh=None) -> bool:
    """Whether a connection on X [n, B, S, D] takes the Pallas calls: on a
    TPU (or under `INTERPRET`), bf16 streams, D a multiple of 128, the
    tokens a multiple of the token tile, at most 6 streams (a stream's columns
    are a group's rows), blocks that fit the VMEM a call gets without
    stating a limit, one device."""
    n, b, s, d = X.shape
    return bool((INTERPRET or jax.default_backend() == "tpu")
                and X.dtype == jnp.bfloat16 and d % _LANES == 0
                and (b * s) % TOKEN_TILE == 0 and n + 2 <= GROUP
                and _resident(n, d) <= _VMEM
                and (mesh is None or mesh.size == 1))


def to_rows(v, n: int):
    """v [n + n + n^2, ...] -> [R, ...]: each group's n rows then zeros."""
    pad = ((0, GROUP - n),) + ((0, 0),) * (v.ndim - 1)
    return jnp.concatenate([jnp.pad(v[g * n:(g + 1) * n], pad)
                            for g in range(n + 2)])


def from_rows(r, n: int):
    """[R, ...] -> [n + n + n^2, ...]: the live rows."""
    return jnp.concatenate([r[GROUP * g:GROUP * g + n] for g in range(n + 2)])


def maps_of(rows, n: int):
    """A call's rows [tiles, R, tile] -> [n + n + n^2, tokens]."""
    return from_rows(rows.transpose(1, 0, 2).reshape(rows.shape[1], -1), n)


def phi_rows(phi):
    """phi [n, D, n + n + n^2] -> [R, n D], a map a row."""
    n, d, m = phi.shape
    return to_rows(phi.transpose(2, 0, 1).reshape(m, n * d), n)


def coef_rows(alpha, b, n: int):
    """alpha [3], b [n + n + n^2] -> [R, 2] float32: a row's alpha and b."""
    by_map = jnp.repeat(alpha.astype(_F32), jnp.array([n, n, n * n]),
                        total_repeat_length=n + n + n * n)
    return to_rows(jnp.stack([by_map, b.astype(_F32)], axis=1), n)


# --------------------------------------------------------------------------
# inside the kernels
# --------------------------------------------------------------------------

def _maps_of(u, spec: Spec):
    """u [R, t] = alpha a + b -> the maps [R, t] (dead rows: anything
    finite, H_res's zero), all float32: `streams.maps` from its
    pre-activations on, H_res a row group at a time."""
    n = spec.n
    pre = jax.nn.sigmoid(u[:GROUP])
    post = 2 * jax.nn.sigmoid(u[GROUP:2 * GROUP])
    live = jax.lax.broadcasted_iota(jnp.int32, pre.shape, 0) < n
    m = [jnp.where(live, jnp.exp(jnp.clip(
        u[GROUP * (2 + i):GROUP * (3 + i)], spec.lo, spec.hi)), 0.0)
        for i in range(n)]
    for _ in range(spec.iters):
        m = [r / (jnp.sum(r, axis=0, keepdims=True) + spec.eps) for r in m]
        # a dead row's sum is 0: its own 0 stays 0 over 1 as over eps, and
        # a compiler that folds the forty divisions into one does not
        # reach 0 / eps^20
        total = jnp.where(live, sum(m[1:], m[0]) + spec.eps, 1.0)
        m = [r / total for r in m]
    return jnp.concatenate([pre, post] + m, axis=0)


def _eye(k: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)).astype(_F32)


def _columns(rows, cols_ref):
    """rows [r, tile] -> `cols_ref` [tile, >= r]: column r is row r, exactly:
    identity x rows^T, float32 rows at `HIGHEST`, bf16 rows as they are."""
    r, tile = rows.shape
    exact = _HIGHEST if rows.dtype == _F32 else None
    cols_ref[:, 0:r] = jax.lax.dot_general(
        _eye(tile).astype(rows.dtype), rows, _NT, precision=exact,
        preferred_element_type=_F32).astype(cols_ref.dtype)


def _row_of_sums(parts, row: int):
    """parts [tile, 128] float32 -> [8, tile]: row `row` the sums over the
    lanes, the others zero (one-hot x parts^T at `HIGHEST`)."""
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (GROUP, _LANES), 0)
              == row).astype(_F32)
    return jax.lax.dot_general(onehot, parts, _NT, precision=_HIGHEST,
                               preferred_element_type=_F32)


def _lane_chunk(d: int) -> int:
    return next(c for c in (512, 256, _LANES) if d % c == 0)


def _fold_lanes(x):
    """[rows, k 128] -> [rows, 128]: the lane tiles added up."""
    out = x[:, :_LANES]
    for at in range(_LANES, x.shape[1], _LANES):
        out = out + x[:, at:at + _LANES]
    return out


def _row_groups(tile: int, body):
    """`body(r0)` for every group of `ROW_GROUP` rows of the tile."""
    from jax.experimental import pallas as pl

    def step(g, carry):
        body(pl.multiple_of(g * ROW_GROUP, ROW_GROUP))
        return carry

    jax.lax.fori_loop(0, tile // ROW_GROUP, step, 0)


def _pre_fwd_kernel(x_ref, phi_ref, coef_ref, h_ref, maps_ref, a_ref,
                    inv_ref, sq_ref, cols_ref, *, spec: Spec):
    from jax.experimental import pallas as pl

    n, tile, d = x_ref.shape
    dc = _lane_chunk(d)
    rows = phi_ref.shape[0]
    # vec(X) . phi: the tokens leave minor, [R, tile]
    s = jnp.zeros((rows, tile), _F32)
    for i in range(n):
        for c in range(0, d, dc):
            s = s + jax.lax.dot_general(
                phi_ref[:, i * d + c:i * d + c + dc], x_ref[i, :, c:c + dc],
                _NT, preferred_element_type=_F32)

    def squares(r0):
        part = jnp.zeros((ROW_GROUP, _LANES), _F32)
        for i in range(n):
            for c in range(0, d, dc):
                x = x_ref[i, pl.ds(r0, ROW_GROUP), c:c + dc].astype(_F32)
                part = part + _fold_lanes(x * x)
        sq_ref[pl.ds(r0, ROW_GROUP), :] = part

    _row_groups(tile, squares)
    mean = _row_of_sums(sq_ref[...], 0)[0:1] / (n * d)
    inv = jax.lax.rsqrt(mean + spec.norm_eps)
    a = s * inv
    coef = coef_ref[...]
    maps = _maps_of(coef[:, 0:1] * a + coef[:, 1:2], spec)
    a_ref[0] = a
    inv_ref[0] = inv
    maps_ref[0] = maps
    _columns(maps, cols_ref)

    def mix(r0):
        cols = cols_ref[pl.ds(r0, ROW_GROUP), :]
        for c in range(0, d, dc):
            h = sum(cols[:, i:i + 1]
                    * x_ref[i, pl.ds(r0, ROW_GROUP), c:c + dc].astype(_F32)
                    for i in range(n))
            h_ref[pl.ds(r0, ROW_GROUP), c:c + dc] = h.astype(h_ref.dtype)

    _row_groups(tile, mix)


def _post_fwd_kernel(x_ref, y_ref, maps_ref, out_ref, cols_ref):
    from jax.experimental import pallas as pl

    n, tile, d = x_ref.shape
    dc = _lane_chunk(d)
    _columns(maps_ref[0], cols_ref)

    def mix(r0):
        at = pl.ds(r0, ROW_GROUP)
        cols = cols_ref[at, :]
        for c in range(0, d, dc):
            x = [x_ref[j, at, c:c + dc].astype(_F32) for j in range(n)]
            y = y_ref[at, c:c + dc].astype(_F32)
            for i in range(n):
                res = GROUP * (2 + i)
                out = cols[:, GROUP + i:GROUP + i + 1] * y + sum(
                    cols[:, res + j:res + j + 1] * x[j] for j in range(n))
                out_ref[i, at, c:c + dc] = out.astype(out_ref.dtype)

    _row_groups(tile, mix)


def _post_bwd_kernel(dout_ref, x_ref, y_ref, maps_ref, dy_ref, dmaps_ref,
                     cols_ref, parts_ref):
    """dy = sum_i H_post[i] dX'[i]; d H_post[i] = <dX'[i], y>, d H_res[i, j]
    = <dX'[i], X[j]>: a token's inner products over D, lane tiles added on
    the vector unit, the 128 lanes on the MXU. H_pre's rows of d(maps):
    zero."""
    from jax.experimental import pallas as pl

    n, tile, d = x_ref.shape
    dc = _lane_chunk(d)
    _columns(maps_ref[0], cols_ref)

    def products(r0):
        at = pl.ds(r0, ROW_GROUP)
        cols = cols_ref[at, :]
        parts = [[jnp.zeros((ROW_GROUP, _LANES), _F32)
                  for _ in range(n + 1)] for _ in range(n)]
        for c in range(0, d, dc):
            x = [x_ref[j, at, c:c + dc].astype(_F32) for j in range(n)]
            x.append(y_ref[at, c:c + dc].astype(_F32))
            dy = jnp.zeros((ROW_GROUP, dc), _F32)
            for i in range(n):
                dout = dout_ref[i, at, c:c + dc].astype(_F32)
                dy = dy + cols[:, GROUP + i:GROUP + i + 1] * dout
                for j in range(n + 1):
                    parts[i][j] = parts[i][j] + _fold_lanes(dout * x[j])
            dy_ref[at, c:c + dc] = dy.astype(dy_ref.dtype)
        for i in range(n):
            for j in range(n + 1):
                parts_ref[i * (n + 1) + j, at, :] = parts[i][j]

    _row_groups(tile, products)
    dmaps_ref[0, 0:GROUP, :] = jnp.zeros((GROUP, tile), _F32)
    dmaps_ref[0, GROUP:2 * GROUP, :] = sum(
        _row_of_sums(parts_ref[i * (n + 1) + n], i) for i in range(n))
    for i in range(n):
        dmaps_ref[0, GROUP * (2 + i):GROUP * (3 + i), :] = sum(
            _row_of_sums(parts_ref[i * (n + 1) + j], j) for j in range(n))


def _halves(x):
    """x [r, t] float32 -> [2 r, t] bf16: its high half over its low one
    (their sum is x to 2^-17 of it)."""
    high = x.astype(jnp.bfloat16)
    low = (x - high.astype(_F32)).astype(jnp.bfloat16)
    return jnp.concatenate([high, low], axis=0)


def _pre_bwd_kernel(dout_ref, x_ref, dh_ref, a_ref, inv_ref, dmaps_ref,
                    phi2_ref, coef_ref, dx_ref, dphi_ref, du_ref, cols_ref,
                    ds2_ref, parts_ref, proj_ref, *, spec: Spec):
    """dX[j] = sum_i H_res[i, j] dX'[i] + H_pre[j] dh + (the norm's term)
    X[j] + ds . phi[j], a stream j a grid step (the grid is token tiles x
    streams, so one stream's dX and ds . phi are resident, not n). A tile's
    FIRST step does what all its streams share: d H_pre[i] = <dh, X[i]>
    joins d(maps); `jax.vjp` of `_maps_of` recomputes the maps and
    Sinkhorn's iterates from a and transposes them; d phi's rows are summed
    over the token tiles in `dphi_ref`; each stream's columns (its column
    of H_res, its H_pre, the norm's factor) go to `cols_ref[j]`."""
    from jax.experimental import pallas as pl

    n, tile, d = x_ref.shape
    dc = _lane_chunk(d)
    rows = a_ref.shape[1]
    j = pl.program_id(1)

    @pl.when((pl.program_id(0) == 0) & (j == 0))
    def _():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)

    @pl.when(j == 0)
    def _():
        def products(r0):
            at = pl.ds(r0, ROW_GROUP)
            parts = [jnp.zeros((ROW_GROUP, _LANES), _F32) for _ in range(n)]
            for c in range(0, d, dc):
                dh = dh_ref[at, c:c + dc].astype(_F32)
                for i in range(n):
                    parts[i] = parts[i] + _fold_lanes(
                        dh * x_ref[i, at, c:c + dc].astype(_F32))
            for i in range(n):
                parts_ref[i, at, :] = parts[i]

        _row_groups(tile, products)
        dmaps = dmaps_ref[0]
        dpre = dmaps[:GROUP] + sum(_row_of_sums(parts_ref[i], i)
                                   for i in range(n))
        dmaps = jnp.concatenate([dpre, dmaps[GROUP:]], axis=0)
        a, inv, coef = a_ref[0], inv_ref[0], coef_ref[...]
        alpha = coef[:, 0:1]
        maps, back = jax.vjp(partial(_maps_of, spec=spec),
                             alpha * a + coef[:, 1:2])
        (du,) = back(dmaps)
        du_ref[0] = du
        da = alpha * du
        ds = da * inv
        # r = (mean + eps)^-1/2: d mean = -r^3 / 2 . sum(da . s), a = s r
        norm = -(inv * inv) * jnp.sum(da * a, axis=0, keepdims=True) / (n * d)
        ds2 = _halves(ds)
        for i in range(n):
            for c in range(0, d, dc):
                both = jax.lax.dot_general(
                    ds2, x_ref[i, :, c:c + dc], _NN,
                    preferred_element_type=_F32)
                at = slice(i * d + c, i * d + c + dc)
                dphi_ref[:, at] = dphi_ref[:, at] + both[:rows] + both[rows:]
        # stream k's columns: H_res[0..n, k], H_pre[k], the norm's factor,
        # picked out of the maps' rows by a one-hot product, exactly
        mapped = jnp.concatenate(
            [maps, jnp.broadcast_to(norm, (GROUP, tile))], axis=0)
        row = jax.lax.broadcasted_iota(jnp.int32, (GROUP, rows + GROUP), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (GROUP, rows + GROUP), 1)
        for k in range(n):
            wanted = jnp.where(row < n, GROUP * (2 + row) + k,
                               jnp.where(row == n, k, rows))
            pick = ((col == wanted) & (row < n + 2)).astype(_F32)
            _columns(jax.lax.dot_general(
                pick, mapped, _NN, precision=_HIGHEST,
                preferred_element_type=_F32), cols_ref.at[k])
        # ds's halves as columns, zeros up to phi2's rows
        k2 = phi2_ref.shape[1]
        _columns(jnp.concatenate(
            [ds2, jnp.zeros((k2 - 2 * rows, tile), ds2.dtype)], axis=0),
            ds2_ref)

    for c in range(0, d, dc):
        proj_ref[:, c:c + dc] = jax.lax.dot_general(
            ds2_ref[...], phi2_ref[0, :, c:c + dc], _NN,
            preferred_element_type=_F32)

    def mix(r0):
        at = pl.ds(r0, ROW_GROUP)
        cols = cols_ref[j, at, :]
        for c in range(0, d, dc):
            out = proj_ref[at, c:c + dc] \
                + cols[:, n:n + 1] * dh_ref[at, c:c + dc].astype(_F32) \
                + cols[:, n + 1:n + 2] * x_ref[j, at, c:c + dc].astype(_F32)
            for i in range(n):
                out = out + cols[:, i:i + 1] \
                    * dout_ref[i, at, c:c + dc].astype(_F32)
            dx_ref[0, at, c:c + dc] = out.astype(dx_ref.dtype)

    _row_groups(tile, mix)


# --------------------------------------------------------------------------
# the calls
# --------------------------------------------------------------------------

def _call(scope, kernel, grid, ins, outs, scratch, interpret,
          sequential=False):
    """`pl.pallas_call` over the token tiles, under `jax.named_scope(scope)`
    as the innermost name (the compiler names the call's instruction, and
    so its events in a trace, by it: `%hc.pre.3`): `ins` / `outs` are (array
    or its shape and dtype, block shape, index map), `scratch` (shape,
    dtype)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    call = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[pl.BlockSpec(b, m) for _, b, m in ins],
        out_specs=[pl.BlockSpec(b, m) for _, b, m in outs],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a, _, _ in outs],
        scratch_shapes=[pltpu.VMEM(s, t) for s, t in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary" if sequential
                                 else "parallel",) * len(grid)),
        interpret=interpret)
    with jax.named_scope(scope):
        return call(*[a for a, _, _ in ins])


_shaped = jax.ShapeDtypeStruct


def _tiles(x, rows):
    """-> (tile, grid, a block of the streams, of one stream, of the rows)."""
    n, t, d = x.shape
    tile = TOKEN_TILE
    return (tile, (t // tile,), ((n, tile, d), lambda i: (0, i, 0)),
            ((tile, d), lambda i: (i, 0)),
            ((1, rows, tile), lambda i: (i, 0, 0)))


def _whole(a):
    """`a` (an array, or its shape and dtype) as one block at every step."""
    return (a, a.shape, lambda *step: (0,) * len(a.shape))


@partial(jax.jit, static_argnames=("spec", "interpret"))
def _pre_fwd(x, phi_r, coef, spec: Spec, interpret=False):
    """-> (h [t, D], the maps' rows, a's rows, 1 / r [tiles, 1, tile])."""
    n, t, d = x.shape
    rows = phi_r.shape[0]
    tile, grid, streams, stream, mapped = _tiles(x, rows)
    lanes = ((tile, _LANES), _F32)
    return _call(
        "hc.pre", partial(_pre_fwd_kernel, spec=spec), grid,
        [(x,) + streams, _whole(phi_r), _whole(coef)],
        [(_shaped((t, d), x.dtype),) + stream,
         (_shaped((t // tile, rows, tile), _F32),) + mapped,
         (_shaped((t // tile, rows, tile), _F32),) + mapped,
         (_shaped((t // tile, 1, tile), _F32), (1, 1, tile),
          lambda i: (i, 0, 0))],
        [lanes, lanes], interpret)


@partial(jax.jit, static_argnames=("interpret",))
def _post_fwd(x, y, maps, interpret=False):
    n, t, d = x.shape
    tile, grid, streams, stream, mapped = _tiles(x, maps.shape[1])
    (out,) = _call(
        "hc.post", _post_fwd_kernel, grid,
        [(x,) + streams, (y,) + stream, (maps,) + mapped],
        [(_shaped(x.shape, x.dtype),) + streams],
        [((tile, _LANES), _F32)], interpret)
    return out


@partial(jax.jit, static_argnames=("interpret",))
def _post_bwd(dout, x, y, maps, interpret=False):
    """-> (dy [t, D], d(maps)'s rows)."""
    n, t, d = x.shape
    rows = maps.shape[1]
    tile, grid, streams, stream, mapped = _tiles(x, rows)
    return _call(
        "hc.post", _post_bwd_kernel, grid,
        [(dout,) + streams, (x,) + streams, (y,) + stream, (maps,) + mapped],
        [(_shaped(y.shape, y.dtype),) + stream,
         (_shaped(maps.shape, _F32),) + mapped],
        [((tile, _LANES), _F32), ((n * (n + 1), tile, _LANES), _F32)],
        interpret)


@partial(jax.jit, static_argnames=("spec", "interpret"))
def _pre_bwd(dout, x, dh, a, inv, dmaps, phi_r, coef, spec: Spec,
             interpret=False):
    """-> (dX, d phi's rows [R, n D] float32, du's rows): the grid is token
    tiles x streams."""
    n, t, d = x.shape
    rows = phi_r.shape[0]
    tile = TOKEN_TILE
    k2 = -(-2 * rows // _LANES) * _LANES
    by_stream = phi_r.reshape(rows, n, d).transpose(1, 0, 2)
    phi2 = jnp.concatenate([by_stream, by_stream, jnp.zeros(
        (n, k2 - 2 * rows, d), phi_r.dtype)], axis=1)
    streams = ((n, tile, d), lambda i, j: (0, i, 0))
    mapped = ((1, rows, tile), lambda i, j: (i, 0, 0))
    return _call(
        "hc.pre", partial(_pre_bwd_kernel, spec=spec), (t // tile, n),
        [(dout,) + streams, (x,) + streams,
         (dh, (tile, d), lambda i, j: (i, 0)), (a,) + mapped,
         (inv, (1, 1, tile), lambda i, j: (i, 0, 0)), (dmaps,) + mapped,
         (phi2, (1, k2, d), lambda i, j: (j, 0, 0)), _whole(coef)],
        [(_shaped(x.shape, x.dtype), (1, tile, d), lambda i, j: (j, i, 0)),
         _whole(_shaped((rows, n * d), _F32)),
         (_shaped(a.shape, _F32),) + mapped],
        [((n, tile, _LANES), _F32), ((tile, k2), jnp.bfloat16),
         ((n, tile, _LANES), _F32), ((tile, d), _F32)],
        interpret, sequential=True)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def pre_mix(x, phi_r, coef, spec: Spec, interpret=False):
    """x [n, t, D] bf16, `phi_rows(phi)`, `coef_rows(alpha, b)` -> (h [t, D],
    the maps' rows [R, t] float32, x for `post_mix` and for nothing else)."""
    h, maps, _, _ = _pre_fwd(x, phi_r, coef, spec, interpret)
    return h, maps, x


def _pre_mix_fwd(x, phi_r, coef, spec, interpret):
    h, maps, a, inv = _pre_fwd(x, phi_r, coef, spec, interpret)
    return (h, maps, x), (x, phi_r, coef, a, inv)


def _pre_mix_bwd(spec, interpret, saved, cotangents):
    x, phi_r, coef, a, inv = saved
    dh, dmaps, dout = cotangents    # dout: dX' as `post_mix` hands it back
    dx, dphi, du = _pre_bwd(dout, x, dh, a, inv, dmaps, phi_r, coef, spec,
                            interpret)
    dcoef = jnp.stack([jnp.sum(du * a, axis=(0, 2)),
                       jnp.sum(du, axis=(0, 2))], axis=1)
    return dx, dphi.astype(phi_r.dtype), dcoef


pre_mix.defvjp(_pre_mix_fwd, _pre_mix_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def post_mix(x, y, maps, interpret=False):
    """`pre_mix`'s x and maps, y [t, D] -> X' [n, t, D]. Its backward gives
    dX' itself for x: `pre_mix`'s backward multiplies it by H_res^T."""
    return _post_fwd(x, y, maps, interpret)


def _post_mix_fwd(x, y, maps, interpret):
    return _post_fwd(x, y, maps, interpret), (x, y, maps)


def _post_mix_bwd(interpret, saved, dout):
    dy, dmaps = _post_bwd(dout, *saved, interpret)
    return dout, dy, dmaps


post_mix.defvjp(_post_mix_fwd, _post_mix_bwd)
