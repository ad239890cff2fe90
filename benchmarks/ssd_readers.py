"""Reader of the state-space kernels' metrics (see `readers.py` for the
contract). It reads a `trace_query` of the metric's own file and the model's
`mamba_heads`, so on a program without these kernels, or under a
configuration without that field, it finds nothing and returns None.
"""

from __future__ import annotations

from benchmarks import peaks
from benchmarks.readers import _opcount, _traced


def kernel_roofline(spec, readings, ctx):
    """`{"trace_query": {"op": regex}, "opcount": "ssd_fwd",
    "events_per_call": 1}`: Pallas kernels of `ops/ssd.py`. A call's shape
    is the cell's own (batch and sequence from the traffic, heads, widths
    and groups from the model); the first event's first output must be one
    of the call's arrays of that shape (y or dx `[b, s, heads x p]`, or the
    backward pass's per-chunk states `[b x groups, chunks x n, heads / groups
    x p]`), or this is another kernel and nothing is read. Calls x the
    opcount module's bound (the larger of the chunked form's matmuls over
    the peak and its operands' bytes, each once, over the HBM's bandwidth)
    over the events' device seconds."""
    q = _traced(readings, ctx)
    model, traffic = ctx["model"], ctx["traffic"]
    if not q or "mamba_heads" not in model or len(q["dims"]) != 3:
        return None
    b, s = traffic["per_chip_batch"], traffic["seq"]
    h, p = model["mamba_heads"], model["mamba_head_dim"]
    groups, n = model["n_groups"], model["state_size"]
    opcount = _opcount(ctx)
    chunks = -(-s // opcount.CHUNK)
    if q["dims"] not in ([b, s, h * p],
                         [b * groups, chunks * n, h // groups * p]):
        return None
    ops, nbytes = getattr(opcount, spec["opcount"])(b, h, s, p, groups, n)
    bound = opcount.bound_seconds(ops, nbytes, peaks.peaks(ctx["device_kind"]))
    calls = q["count"] / spec.get("events_per_call", 1)
    return 100.0 * calls * bound / q["total_s"]
