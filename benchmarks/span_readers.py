"""Readers of the program's own spans (`ray_tpu/_private/device_profiler`).

The benchmark's parent is the driver of the cell: `ray_tpu.init()` and
`JaxTrainer(...).fit()` run in it, so the driver-side spans (`cluster.init`,
`train.gang.*`) and rank 0's start-up spans that the trainer merges under
them (`train.worker.*`) are in THIS process's aggregate, which outlives
`ray_tpu.shutdown()`. A reader takes the aggregate from
`readings["spans"]` where a cell's kind passes one, else from
`device_profiler.snapshot()`; it imports no jax. Where the program has no
span layer (a commit before it) or left no such span, a reader returns None
and the metric is left out of the line.

A metric's file gives `"spans"` (names whose seconds are added) and
`"field"` (`total_s`, or `self_s`: the span's time less what its children,
merged worker spans among them, cover), or for `covered_share` a `"prefix"`
and the path of the outside timing it is held against.
"""

from __future__ import annotations


def _aggregate(readings: dict):
    """{name: {count, total_s, max_s, self_s}} or None."""
    given = readings.get("spans")
    if given is not None:
        return given or None
    try:
        from ray_tpu._private import device_profiler

        return device_profiler.snapshot()["spans"] or None
    except (ImportError, AttributeError, KeyError, TypeError):
        return None  # the program has no span layer


def span_seconds(spec, readings, ctx):
    """`{"spans": ["train.gang.session", "train.gang.launch"],
    "field": "total_s"}`: seconds of the named spans, added."""
    spans = _aggregate(readings)
    if not spans:
        return None
    found = [spans[name][spec.get("field", "total_s")]
             for name in spec["spans"] if name in spans]
    return sum(found) if found else None


def covered_share(spec, readings, ctx):
    """`{"prefix": "train.gang.", "over": "host.trainer_start_s"}`: the
    spans under the prefix (siblings: none nests in another) as a percentage
    of a timing taken from outside. Not clamped: a last span that ends a
    little after the outside timing does reads a little over 100."""
    spans = _aggregate(readings)
    over = readings
    for part in spec["over"].split("."):
        over = over.get(part) if isinstance(over, dict) else None
    if not spans or not over:
        return None
    found = [v["total_s"] for name, v in spans.items()
             if name.startswith(spec["prefix"])]
    return 100.0 * sum(found) / over if found else None
