"""How a model whose layers repeat in periods runs the ones it holds:
the segmentation `models/hybrid_moe.py` and `models/window_moe.py` share."""

from __future__ import annotations


def segments(held, n_dense: int, period: int, start: int = 0):
    """-> (dense, loose, periods, segments): the held layers' published
    indices `held` (in order), split into the dense ones (below `n_dense`),
    the layers that run unrolled and the first indices of the whole ALIGNED
    periods (`period` consecutive held layers from an index = `start` mod
    `period` on, none of them dense), each in order; and the execution
    order as segments ("dense", n), ("loose", n), ("periods", n) of
    consecutive layers / periods."""
    dense = [i for i in held if i < n_dense]
    rest = [i for i in held if i >= n_dense]
    have, loose, periods, out = set(rest), [], [], []

    def add(kind):
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])

    for _ in dense:
        add("dense")
    i = 0
    while i < len(rest):
        first = rest[i]
        if (first - start) % period == 0 and all(
                first + j in have for j in range(period)):
            periods.append(first)
            add("periods")
            i += period
        else:
            loose.append(first)
            add("loose")
            i += 1
    return dense, loose, periods, [tuple(s) for s in out]
