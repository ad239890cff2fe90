"""Grouped matmul over ragged groups: the experts' matmul of a dropless MoE.

`grouped_matmul(lhs [M, K], rhs [G, K, N], group_sizes [G])` multiplies the
rows of group g (rows are sorted by group; the sizes sum to M) by `rhs[g]`.
Its backward is two more of the same ops: `grad x rhs[g]^T` over the same
groups for the rows, and the per-group `lhs_g^T x grad_g` for the weights.

On a TPU these are jax's Pallas megablox kernels (`gmm`, `tgmm`) with the
tilings below; elsewhere `lax.ragged_dot`, whose gradients autodiff gives.
Both were timed on the v5e at [65536, 2048] x [64, 2048, 1024] and
[65536, 1024] x [64, 1024, 2048] bf16 (PERF.md §6, PR 27): forward +
backward of one matmul is 10.4 ms through `ragged_dot` (3.0-3.2 forward,
7.2-7.4 backward) and 6.4 ms through megablox at these tilings (2.0-2.06
forward and rows' gradient, 2.36-2.38 weights' gradient), against 1.40 ms
a call at the chip's peak. megablox's default tiling (128, 128, 128) takes
26.7 ms a call.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from ray_tpu._private import device_profiler


def _gmm_tiling(k: int, n: int):
    """(tm, tk, tn) for `gmm`: 256 rows against an expert's WHOLE [K, N]
    matrix where it is at most 4 MiB of bf16 (the cell's are exactly that),
    so the contraction needs no accumulation across grid steps and each
    expert's weights are read once. Fastest of twenty candidates for both
    of the cell's shapes, transposed or not. Where K is no power of two
    (an expert of 1,280: 2 ** 21 // 1280 = 1638) the column tile is
    rounded down to whole lanes, which Mosaic asks of a block that is not
    the whole array."""
    tk = min(k, 2048)
    tn = 2 ** 21 // tk
    return 256, tk, n if n <= tn else tn // 128 * 128


# 256 rows of a group a step into a [1024, 1024] tile of its [K, N] output
_TGMM_TILING = (256, 1024, 1024)


def _megablox():
    # the package's __init__ shadows the module `gmm` with the function
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _clamp(tiling, m, k, n):
    tm, tk, tn = tiling
    return min(tm, m), min(tk, k), min(tn, n)


def _pad_rows(x, tm):
    """megablox needs M divisible by the row tile. Rows added here are past
    the last group: no group visits them."""
    pad = -x.shape[0] % tm
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _count_tiles(m, tm, tiles_out, groups):
    # Per lowering, as `flash.steps_*`. The plan itself is made on the
    # device from the group sizes: a row tile that a group boundary cuts is
    # visited once for each group in it, so at most `groups - 1` visits a
    # column of tiles are the ragged waste.
    device_profiler.count("moe.gmm_tiles", -(-m // tm) * tiles_out)
    device_profiler.count("moe.gmm_tiles_partial", (groups - 1) * tiles_out)


def _gmm(lhs, rhs, group_sizes, transpose_rhs, interpret=False):
    """-> [M, N]: rows of group g x rhs[g], or x rhs[g]^T. `interpret` runs
    the kernel in the Pallas interpreter (the CPU tests)."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiling = _clamp(_gmm_tiling(k, n), m, k, n)
    _count_tiles(m, tiling[0], -(-n // tiling[2]), rhs.shape[0])
    out = _megablox().gmm(_pad_rows(lhs, tiling[0]), rhs, group_sizes,
                          lhs.dtype, tiling, transpose_rhs=transpose_rhs,
                          interpret=interpret)
    return out[:m]


def _tgmm(lhs, grad, group_sizes, interpret=False):
    """-> [G, K, N], the per-group lhs_g^T x grad_g."""
    m, k = lhs.shape
    n = grad.shape[1]
    tiling = _clamp(_TGMM_TILING, m, k, n)
    _count_tiles(m, tiling[0], -(-k // tiling[1]) * -(-n // tiling[2]),
                 group_sizes.shape[0])
    return _megablox().tgmm(
        _pad_rows(lhs, tiling[0]).swapaxes(0, 1), _pad_rows(grad, tiling[0]),
        group_sizes, lhs.dtype, tiling, interpret=interpret)


@jax.custom_vjp
def _gmm_tpu(lhs, rhs, group_sizes):
    return _gmm(lhs, rhs, group_sizes, transpose_rhs=False)


def _gmm_tpu_fwd(lhs, rhs, group_sizes):
    return _gmm_tpu(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_tpu_bwd(res, grad):
    lhs, rhs, group_sizes = res
    return (_gmm(grad, rhs, group_sizes, transpose_rhs=True),
            _tgmm(lhs, grad, group_sizes).astype(rhs.dtype), None)


_gmm_tpu.defvjp(_gmm_tpu_fwd, _gmm_tpu_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """-> [M, N] in lhs.dtype, accumulated in float32. Which path runs is
    read off the platform, as `flash_attention` does."""
    if jax.default_backend() == "tpu":
        return _gmm_tpu(lhs, rhs, group_sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)
