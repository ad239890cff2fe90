"""Sum the rows of a buffer into their tokens: the combine of a share.

`sum_rows_by_token(rows [cap, D], token [cap], slot [T, k], live)` adds
up, for each of T tokens, the rows of `rows` that belong to it. `token[s]` is
the token of row s, or T for a DEAD row (the rows from `live` on);
`slot[t, j]` is the row of token t's j-th pair, or a dead row where that
pair's expert is absent. Dead rows are read by neither form below, so they
may hold anything (the grouped matmuls leave them unwritten).

A chip that holds 1/8 of the experts sees one live pair a token on average
where `slot` has k = 8: gathering `rows[slot]` moves T x k rows to add up
the ~T that landed (`fusion_bf16_131072_2048`, 13.1% of train-sdar-1chip's
step: PERF.md section 5, PR 34). On a TPU the work follows the LIVE rows
instead: a sort of the `cap` token ids brings the live rows into token
order, first; a gather of the row tiles that hold one moves them
(`ops/row_moves.py`, told `live`, the number of live rows: a plain gather
of `cap` rows until PR 45), and each tile of `_TOKEN_TILE`
tokens, whose rows are then one contiguous range, is summed on the MXU as
onehot^T x rows by the megablox `tgmm` kernel `grouped_matmul.py` wraps
(groups = token tiles, no scatter; a tile with no row comes out zero, rows
past the last group are visited by nothing). A bf16 one times a bf16 row
accumulated in float32 is that row, so the result is the float32 sum of a
token's rows rounded once, as the gather form's; elsewhere, and for a
dtype the MXU would round, the gather form stands (PERF.md section 6,
PR 35, has both forms' times).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.ops.grouped_matmul import _clamp, _megablox, _pad_rows
from ray_tpu.ops.row_moves import take_live_rows

# tokens a group: the one-hot's width, and the rows of an output tile
_TOKEN_TILE = 256
# rows a step against ALL of D's columns up to 2,048: within 0.02 ms of the
# best of twelve tilings at [32768, 2048] and [16384, 2048] bf16 on the v5e
# (0.40, 0.23 ms; two column tiles of 1,024 read the one-hot twice: 0.47, 0.25)
_ROW_TILE, _COLUMN_TILE = 256, 2048


@partial(jax.jit, static_argnames=("t", "interpret"))
def _sum_in_token_order(rows, token, live, t: int, interpret=False):
    """-> [T, D] in rows.dtype. `interpret` runs the kernel in the Pallas
    interpreter (the CPU tests). Jitted as megablox's own entry points are:
    lowering a step traces each capacity's combine four times (the
    `custom_vjp`'s primal and its forward rule, twice more on the way back),
    and the kernel's plan of visits is 70 ms of tracing a time."""
    cap, d = rows.shape
    tile = min(_TOKEN_TILE, t)
    n_tiles = -(-t // tile)
    # live rows first, by token; dead rows (token T) last
    token_sorted, perm = jax.lax.sort_key_val(
        token, jnp.arange(cap, dtype=jnp.int32))
    by_token = take_live_rows(rows, perm, live)
    # rows of each token tile without a scatter, as `sort_held` counts its
    # groups: where each tile's run starts among the sorted tokens
    bounds = jnp.minimum(jnp.arange(n_tiles + 1) * tile, t)
    starts = jnp.sum(token_sorted[None, :] < bounds[:, None], axis=1)
    sizes = jnp.diff(starts).astype(jnp.int32)
    onehot = ((token_sorted % tile)[:, None]
              == jnp.arange(tile)[None, :]).astype(rows.dtype)
    tm, tk, tn = _clamp((_ROW_TILE, tile, _COLUMN_TILE), cap, tile, d)
    sums = _megablox().tgmm(
        _pad_rows(onehot, tm).swapaxes(0, 1), _pad_rows(by_token, tm),
        sizes, rows.dtype, (tm, tk, tn), interpret=interpret)
    return sums.reshape(n_tiles * tile, d)[:t]


def sum_rows_by_token(rows, token, slot, live):
    """-> [T, D]: the sum of each token's live rows, accumulated in float32,
    in rows.dtype. Which form runs is read off the platform and the dtype,
    as `grouped_matmul` picks its kernels."""
    t = slot.shape[0]
    if jax.default_backend() == "tpu" and rows.dtype == jnp.bfloat16:
        return _sum_in_token_order(rows, token, live, t)
    picked = jnp.where((token[slot] < t)[..., None],
                       rows[slot].astype(jnp.float32), 0.0)
    return jnp.sum(picked, axis=1).astype(rows.dtype)
