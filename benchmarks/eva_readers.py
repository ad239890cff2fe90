"""Reader of the EVA cell's kernel metrics (see `readers.py` for the
contract). It reads a `trace_query` of the metric's own file and the
model's `window` and `chunk`, so on a program without this flash call, or
under a configuration without those fields, it finds nothing and returns
None.
"""

from __future__ import annotations

from benchmarks import peaks
from benchmarks.readers import _opcount, _traced


def flash_roofline(spec, readings, ctx):
    """`{"trace_query": {"op": regex}, "opcount": "eva_flash_fwd",
    "events_per_call": 1}`: the flash call under `EvaWindows`, whose events
    carry its scope's name. [b, h, rows, d] are read off the first event's
    (first) output, a chip's share under sharding; its rows are the queries'
    S (o, dq) or the keys' S + S / chunk (dk), so S is the traffic's and the
    event has to be one of the two, or this is not that call. The bound
    counts the scores the rule KEEPS, both kinds: calls x the opcount
    module's bound over the events' device seconds."""
    q = _traced(readings, ctx)
    model = ctx["model"]
    if not q or "window" not in model or "chunk" not in model \
            or len(q["dims"]) != 4:
        return None
    b, h, rows, d = q["dims"]
    s = ctx["traffic"]["seq"]
    if rows not in (s, s + s // model["chunk"]):
        return None
    opcount = _opcount(ctx)
    ops, nbytes = getattr(opcount, spec["opcount"])(
        b, h, s, d, model["window"], model["chunk"])
    bound = opcount.bound_seconds(ops, nbytes, peaks.peaks(ctx["device_kind"]))
    calls = q["count"] / spec.get("events_per_call", 1)
    return 100.0 * calls * bound / q["total_s"]
