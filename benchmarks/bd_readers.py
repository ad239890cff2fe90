"""Reader of the block-diffusion cell's kernel metrics (see `readers.py` for
the contract). It reads a `trace_query` of the metric's own file and the
model's `block`, so on a program without this flash call, or under a
configuration without that field, it finds nothing and returns None.
"""

from __future__ import annotations

from benchmarks import peaks
from benchmarks.readers import _opcount, _traced


def flash_roofline(spec, readings, ctx):
    """`{"trace_query": {"op": regex}, "opcount": "bd_flash_fwd",
    "events_per_call": 1}`: the flash call under the block-diffusion mask.
    [b, h, s, d] are read off the first event's (first) output, a chip's
    share under sharding; s is the 2 x length concatenation, and the bound
    counts the scores the mask KEEPS (length^2 + length x block a (batch,
    head), the model's `block`), not a causal half: calls x the opcount
    module's bound over the events' device seconds."""
    q = _traced(readings, ctx)
    model = ctx["model"]
    if not q or "block" not in model or len(q["dims"]) != 4:
        return None
    b, h, s, d = q["dims"]
    opcount = _opcount(ctx)
    ops, nbytes = getattr(opcount, spec["opcount"])(
        b, h, s, d, model["block"], model["n_kv_heads"] / model["n_heads"])
    bound = opcount.bound_seconds(ops, nbytes, peaks.peaks(ctx["device_kind"]))
    calls = q["count"] / spec.get("events_per_call", 1)
    return 100.0 * calls * bound / q["total_s"]
