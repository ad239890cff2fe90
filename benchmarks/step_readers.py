"""Readers of the record the train step keeps of every step
(`device_profiler.StepCadence`: `train.step.interval`, `train.step.off_cpu`),
where one span's seconds are read against another's. The spans ride home to
the benchmark's parent on `finish()` as every span of the gang worker does,
and `span_readers._aggregate` finds them there; jax is not imported. Where a
name is missing (a commit before the cadence) or counted nothing, a reader
returns None and the metric is left out of the line.
"""

from __future__ import annotations

from benchmarks.span_readers import _aggregate


def mean(spec, readings, ctx):
    """`{"span": "train.step.interval", "scale": 1000}`: the span's seconds
    over its count, scaled."""
    spans = _aggregate(readings)
    got = (spans or {}).get(spec["span"])
    if not got or not got["count"]:
        return None
    return spec.get("scale", 1) * got["total_s"] / got["count"]


def share_outside(spec, readings, ctx):
    """`{"part": "train.step.off_cpu", "whole": "train.step.interval"}`:
    100 x (1 - part / whole), both `total_s`: the percentage of the whole
    that the part does NOT cover."""
    spans = _aggregate(readings) or {}
    part, whole = spans.get(spec["part"]), spans.get(spec["whole"])
    if not part or not whole or not whole["total_s"]:
        return None
    return 100.0 * (1.0 - part["total_s"] / whole["total_s"])
