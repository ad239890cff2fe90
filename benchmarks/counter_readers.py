"""Readers of the program's own counters (`device_profiler.count`).

The counters of the gang worker (what it lowered: `flash.steps_*`,
`moe.gmm_tiles*`, ...) ride home to the driver on `finish()` and are merged
into its aggregate; the benchmark's parent is that driver, so after the run
they are in THIS process's `device_profiler.snapshot()["counters"]`, as the
spans are that `span_readers.py` reads. A reader takes them from
`readings["counters"]` where a cell's kind passes them, else from the
snapshot; it imports no jax. Where the program has no span layer, brought
no counters home (a commit before that) or lacks a name, a reader returns
None and the metric is left out of the line.
"""

from __future__ import annotations


def _counters(readings: dict):
    """{name: n} or None."""
    given = readings.get("counters")
    if given is not None:
        return given or None
    try:
        from ray_tpu._private import device_profiler

        return device_profiler.snapshot()["counters"] or None
    except (ImportError, AttributeError, KeyError, TypeError):
        return None  # the program has no span layer


def ratio(spec, readings, ctx):
    """`{"over": ["flash.steps_unmasked"], "under": ["flash.steps_unmasked",
    "flash.steps_masked"], "scale": 100}`: the named counters added up, one
    sum over the other. None where a name is missing or the lower sum 0."""
    counters = _counters(readings)
    names = spec["over"] + spec["under"]
    if not counters or any(name not in counters for name in names):
        return None
    under = sum(counters[name] for name in spec["under"])
    if not under:
        return None
    return spec.get("scale", 1) * sum(
        counters[name] for name in spec["over"]) / under
