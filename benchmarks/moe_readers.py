"""Readers of the routed-experts layers' metrics (see `readers.py` for the
contract). Both read a `trace_query` of the metric's own file, so on a
program without these kernels or ops they find nothing and return None.
"""

from __future__ import annotations

import math

from benchmarks import peaks
from benchmarks.readers import _opcount, _traced


def gmm_roofline(spec, readings, ctx):
    """`{"trace_query": {"op": regex}}`: the grouped-matmul kernels' events
    (forward, rows' gradient, weights' gradient: one event a call). Every
    call of a layer has the same operands up to order ([m, k], [groups, k,
    n], [m, n] with m = the chip's tokens x experts per token, k = d_model,
    n = the expert width), so one bound serves all: calls x bound over the
    events' device seconds. The first event's output must be one of the
    three operands, or this is not that kernel and nothing is read."""
    q = _traced(readings, ctx)
    if not q:
        return None
    model, traffic = ctx["model"], ctx["traffic"]
    m = traffic["per_chip_batch"] * traffic["seq"] * model["experts_per_token"]
    k, n, groups = model["d_model"], model["d_ff"], model["n_experts"]
    if math.prod(q["dims"]) not in (m * k, m * n, groups * k * n):
        return None
    opcount = _opcount(ctx)
    ops, nbytes = opcount.moe_gmm(m, k, n, groups)
    bound = opcount.bound_seconds(ops, nbytes, peaks.peaks(ctx["device_kind"]))
    return 100.0 * q["count"] * bound / q["total_s"]


def op_time_share(spec, readings, ctx):
    """`{"trace_query": {"op": regex}}`: device seconds of the matching ops
    as a percentage of the traced window."""
    q = _traced(readings, ctx)
    if not q:
        return None
    return 100.0 * q["total_s"] / readings["trace"]["window_s"]
