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

`sum_rows_by_index(rows [T, D], index [T], n)` is the same sum for rows that
are ALL live and carry their destination: row i of its [n, D] result is the
sum of the rows whose index is i, zero where none is. It is the gradient of
a gather of rows, `table[index]`, by the table (`models/blocks.embed_rows`):
XLA's scatter-add puts one row after the other into a zero table (1 us a
row of 2,560 on the v5e, 0.12-0.34 us a row of 2,048 or 4,096: 2.2 to 9.1
times the sorted sum's time at the ten cells' shapes), and a bf16 one rounds
after every row (PERF.md section 6, PR 56, has both forms' times; which
widths take which form: `sums_by_index_in_order`). With no dead row to
skip, the rows are gathered whole: the loop over row tiles carries buffers
of its own, which a step at the edge of HBM has no room for.
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
    """-> [T, D] in rows.dtype. `live` None: every row is live. `interpret`
    runs the kernel in the Pallas interpreter (the CPU tests). Jitted as
    megablox's own entry points are: lowering a step traces each capacity's
    combine four times (the `custom_vjp`'s primal and its forward rule,
    twice more on the way back), and the kernel's plan of visits is 70 ms of
    tracing a time."""
    cap, d = rows.shape
    tile = min(_TOKEN_TILE, t)
    n_tiles = -(-t // tile)
    # live rows first, by token; dead rows (token T) last
    token_sorted, perm = jax.lax.sort_key_val(
        token, jnp.arange(cap, dtype=jnp.int32))
    # every row live (`live` None): one plain gather moves them, no loop
    by_token = rows.at[perm].get(mode="promise_in_bounds") if live is None \
        else take_live_rows(rows, perm, live)
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


def sums_in_order(dtype) -> bool:
    """Whether a sum of rows of `dtype` takes the sorted form here: read off
    the platform and the dtype, as `grouped_matmul` picks its kernels."""
    return jax.default_backend() == "tpu" and dtype == jnp.bfloat16


def sum_rows_by_token(rows, token, slot, live):
    """-> [T, D]: the sum of each token's live rows, accumulated in float32,
    in rows.dtype."""
    t = slot.shape[0]
    if sums_in_order(rows.dtype):
        return _sum_in_token_order(rows, token, live, t)
    picked = jnp.where((token[slot] < t)[..., None],
                       rows[slot].astype(jnp.float32), 0.0)
    return jnp.sum(picked, axis=1).astype(rows.dtype)


def sums_by_index_in_order(dtype, width: int) -> bool:
    """Whether `sum_rows_by_index` takes the sorted form for rows [., width]:
    where a share's combine does, and XLA's scatter-add pays by the row. On
    the v5e it adds a row whose width is a power of two, or three times one,
    in 0.06-0.23 us (1,024, 1,536, 2,048, 3,072, 4,096) and any other tried
    in 0.5-3.6 us (2,560, 3,584, 4,608, 5,120; 6,144 reads 0.63):
    `tools/embed_grad_chip_check.py --widths`. Where the scatter is fast the
    sorted sum is still 2-4 times faster alone, but it is 1-2 ms of a step,
    and as another program at the end of the backward pass it cost
    `train-1chip` 11 ms: the compiler kept one stacked weight fewer in fast
    memory through the layers' loop (PERF.md section 6, PR 56)."""
    odd = width // (width & -width)
    return sums_in_order(dtype) and odd > 3


def sum_rows_by_index(rows, index, n: int):
    """-> [n, D] in rows.dtype: the sum of the rows of each index in
    [0, n); a row whose index is n or more is dropped by either form. Sorted,
    a float32 sum rounded once; elsewhere the scatter-add, which rounds in
    rows.dtype after every row."""
    if sums_by_index_in_order(rows.dtype, rows.shape[1]):
        return _sum_in_token_order(rows, index, None, n)
    return jnp.zeros((n, rows.shape[1]), rows.dtype).at[index].add(rows)
