"""Gather the rows of a share's buffer that landed, a tile of rows at a time.

`take_live_rows(x [S, D], index [cap], live)` is `x[index]` for a buffer whose
LIVE rows are its first `live` ones (`parallel/moe.sort_held` puts the held
pairs first, `ops/row_sums.py`'s sort by token puts the dead marker last):
only the `ceil(live / tile)` row tiles that hold a live row are gathered, by
a loop whose trip count is read off `live` on the device. A share's buffer is
twice the even share (`share_capacities`), so on average half of its rows are
dead, and a gather is paid by the row: 13 ns for a 4 KiB row on the v5e
(PERF.md section 6, PR 45).

What a caller may count on: rows below `live` are `x[index[s]]` bit for bit;
dead rows inside the last live tile are `x[index[s]]` too, so `index` has to
be in range THERE as everywhere (callers clamp their dead marker: some row's
finite values, read by nothing that is not masked); rows past the last live
tile are zero, or on a TPU UNWRITTEN: whatever the memory held, NaN as well,
so a caller there must visit live tiles only, as the grouped matmuls do. A
buffer of one tile lowers to the plain gather, no loop: the loop is there by
the buffer's shape, not by a switch.

On a TPU the loop's two parts beside the gather are Pallas calls: the buffer
is the output of a kernel with no body (`_unwritten`: XLA's own zeros are a
0.2-0.3 ms write of 128 MiB before a `[32768, 2048]` gather starts), and a
gathered tile, which the compiler puts in fast memory, goes into it by one
DMA (`_copied_in`: 0.026 ms for a 16 MiB tile where XLA's
`dynamic-update-slice` copies it in 0.072). A Pallas kernel cannot do the
gather itself: two bf16 rows share every 32-bit word of the tiled HBM
layout, and Mosaic refuses a slice of fewer than 8 rows.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# rows a loop step gathers: one constant for every buffer (`row_tiles`)
_ROW_TILE = 4096


def row_tiles(cap: int, tile: int = _ROW_TILE) -> int:
    """The row tiles a buffer of `cap` rows is gathered in; 1 = whole, by
    the plain gather."""
    return -(-cap // min(cap, tile))


def _unwritten(shape, dtype, interpret=False):
    """A buffer nothing has written, for no device time: a Pallas call
    whose body is empty. XLA has no such thing of its own (`jnp.empty` is a
    broadcast of zeros: 128 MiB written for a `[32768, 2048]` bf16 buffer,
    0.2 ms on the v5e). In the interpreter it comes out NaN throughout."""
    from jax.experimental import pallas as pl

    return pl.pallas_call(
        lambda out: None, out_shape=jax.ShapeDtypeStruct(shape, dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY), name="unwritten",
        interpret=interpret)()


def _buffer(shape, dtype):
    """What the loop writes its tiles into: unwritten on a TPU, where every
    reader of a share's buffers visits live tiles only (the grouped matmuls
    by their groups, the elementwise work behind a `where`); zeros
    elsewhere, where `lax.ragged_dot` may read every row."""
    if jax.default_backend() == "tpu":
        return _unwritten(shape, dtype)
    return jnp.zeros(shape, dtype)


def _copied_in(buf, rows, start, interpret=False):
    """`buf` with `rows` [tile, D] at row `start` (a multiple of 8), in
    place: ONE DMA of the tile, HBM to HBM, by a Pallas call whose output is
    `buf` itself."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(start_ref, buf_ref, rows_ref, out_ref, done):
        del buf_ref  # `out_ref` is the same memory
        copy = pltpu.make_async_copy(rows_ref, out_ref.at[pl.ds(
            pl.multiple_of(start_ref[0], 8), rows.shape[0])], done)
        copy.start()
        copy.wait()

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        input_output_aliases={1: 0}, name="row_tile", interpret=interpret,
    )(jnp.reshape(start, (1,)), buf, rows)


def _placed(buf, rows, start):
    """`buf` with the gathered tile `rows` at row `start`, by the platform's
    cheaper copy, as `_buffer` picks what it is copied into."""
    if jax.default_backend() == "tpu":
        return _copied_in(buf, rows, start)
    return jax.lax.dynamic_update_slice(buf, rows, (start, 0))


def take_live_rows(x, index, live, tile: int = _ROW_TILE):
    """-> [cap, D] in x.dtype (module docstring). `live` an int32 scalar on
    the device; `tile` is for the chip probe (`tools/row_moves_chip_check.py`),
    the program passes none."""
    cap, = index.shape
    if row_tiles(cap, tile) == 1:
        return x.at[index].get(mode="promise_in_bounds")

    def gather(i, buf):
        # the last tile of a `cap` that `tile` does not divide starts early
        # and gathers some rows twice, to the same values
        start = jnp.minimum(i * tile, cap - tile)
        rows = x.at[jax.lax.dynamic_slice(index, (start,), (tile,))].get(
            mode="promise_in_bounds")
        return _placed(buf, rows, start)

    return jax.lax.fori_loop(0, (live + tile - 1) // tile, gather,
                             _buffer((cap, x.shape[1]), x.dtype))
