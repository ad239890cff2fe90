"""Reader of the delta-rule kernels' metrics (see `readers.py` for the
contract). It reads a `trace_query` of the metric's own file and the model's
`kda_head_dim`, so on a program without these kernels, or under a
configuration without that field, it finds nothing and returns None.
"""

from __future__ import annotations

from benchmarks import peaks
from benchmarks.readers import _opcount, _traced


def kernel_roofline(spec, readings, ctx):
    """`{"trace_query": {"op": regex}, "opcount": "kda_fwd",
    "events_per_call": 1}`: Pallas kernels of `ops/kda.py`. The first
    event's first output is `[batch x heads, s, d_v]`
    (the kernels walk a flat (batch, head) axis), so rows and s are read
    off the first event and split by the model's `n_heads`; the widths are
    the model's. Calls x the opcount module's bound (the larger of the
    chunked form's matmuls over the peak and its operands' bytes, each
    once, over the HBM's bandwidth) over the events' device seconds."""
    q = _traced(readings, ctx)
    model = ctx["model"]
    if not q or "kda_head_dim" not in model or len(q["dims"]) != 3:
        return None
    rows, s, d = q["dims"]
    if d != model["kda_head_dim"] or rows % model["n_heads"]:
        return None
    opcount = _opcount(ctx)
    ops, nbytes = getattr(opcount, spec["opcount"])(
        rows // model["n_heads"], model["n_heads"], s, d, d)
    bound = opcount.bound_seconds(ops, nbytes, peaks.peaks(ctx["device_kind"]))
    calls = q["count"] / spec.get("events_per_call", 1)
    return 100.0 * calls * bound / q["total_s"]
