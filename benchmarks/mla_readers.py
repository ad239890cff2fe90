"""Readers of the latent-attention cell's kernel metrics (see `readers.py`
for the contract). They read a `trace_query` of the metric's own file and
the model's MLA fields, so on a program without this flash call, or under a
configuration without those fields, they find nothing and return None.
"""

from __future__ import annotations

from benchmarks import peaks
from benchmarks.readers import _opcount, _traced


def flash_roofline(spec, readings, ctx):
    """`{"trace_query": {"op": regex}, "opcount": "flash_fwd",
    "events_per_call": 1}`: the flash call whose keys are wider than its
    values. [b, h, s] are read off the first event's (first) output, whose
    last dim must be one of the call's two widths (the forward's output has
    the value width, dq and dk the score width), or this is another kernel
    and nothing is read; the widths themselves are the model's (an event
    names its outputs only). Calls x the opcount module's bound (causal
    half) over the events' device seconds."""
    q = _traced(readings, ctx)
    model = ctx["model"]
    if not q or "qk_nope_head_dim" not in model or len(q["dims"]) != 4:
        return None
    d_qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    d_v = model["v_head_dim"]
    b, h, s, d = q["dims"]
    if d not in (d_qk, d_v):
        return None
    opcount = _opcount(ctx)
    ops, nbytes = getattr(opcount, spec["opcount"])(b, h, s, d_qk, d_v)
    bound = opcount.bound_seconds(ops, nbytes, peaks.peaks(ctx["device_kind"]))
    calls = q["count"] / spec.get("events_per_call", 1)
    return 100.0 * calls * bound / q["total_s"]
