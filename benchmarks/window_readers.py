"""Reader of the window-attention cell's kernel metrics (see `readers.py` for
the contract). It reads a `trace_query` of the metric's own file and the
model's `window`, so on a program without this flash call, or under a
configuration without that field, it finds nothing and returns None.
"""

from __future__ import annotations

from benchmarks import peaks
from benchmarks.readers import _opcount, _traced


def flash_roofline(spec, readings, ctx):
    """`{"trace_query": {"op": regex}, "opcount": "swa_flash_fwd",
    "events_per_call": 1}`: the flash call under the window rule, whose
    events carry its scope's name. [b, h, s, d] are read off the first
    event's (first) output, a chip's share under sharding; the bound counts
    the scores the window KEEPS (min(t + 1, window) a row, the model's
    `window`), not a causal half: calls x the opcount module's bound over
    the events' device seconds."""
    q = _traced(readings, ctx)
    model = ctx["model"]
    if not q or "window" not in model or len(q["dims"]) != 4:
        return None
    b, h, s, d = q["dims"]
    opcount = _opcount(ctx)
    ops, nbytes = getattr(opcount, spec["opcount"])(
        b, h, s, d, model["window"], model["n_kv_heads"] / h)
    bound = opcount.bound_seconds(ops, nbytes, peaks.peaks(ctx["device_kind"]))
    calls = q["count"] / spec.get("events_per_call", 1)
    return 100.0 * calls * bound / q["total_s"]
