"""Readers: how a metric's file turns a run's readings into its number.

A metric's file (`benchmarks/metrics/<name>.json`) names its reader as
`"reader": "<module>.<function>"` (a module under `benchmarks/`) and gives
that function's parameters beside it. A reader gets `(spec, readings, ctx)`:
`readings` is what the cell's kind gathered (`client`, `stats`, `host`,
`trace`: see `train_cell.py`, `serve_cell.py`), `ctx` has `chips`, `model`
(the program's config fields), `opcount` (the configuration's module of
operation counts, under `benchmarks/`), `device_kind`, `seconds`, `setup_s`. A reader that
finds nothing to read returns None and the metric is left out of the line.
A later PR adds a metric as one such file, and a new reader as a new module.
"""

from __future__ import annotations

import importlib
import re

from benchmarks import loadgen, peaks


def _opcount(ctx):
    return importlib.import_module("benchmarks." + ctx["opcount"])


def _get(readings: dict, path: str):
    node = readings
    for part in path.split("."):
        if not isinstance(node, dict) or part not in node:
            return None
        node = node[part]
    return node


def _traced(readings, ctx):
    """What `reduce_trace` found for this metric's `trace_query`."""
    return ((readings.get("trace") or {}).get("queries") or {}).get(
        ctx["name"])


def value(spec, readings, ctx):
    """`{"path": "host.trainer_start_s", "scale": 1}`: a scalar as read."""
    v = _get(readings, spec["path"])
    return None if v is None else v * spec.get("scale", 1)


def setup(spec, readings, ctx):
    return ctx["setup_s"]


def quantile(spec, readings, ctx):
    """`{"path": "client.ttft_ms", "q": 0.9, "min_count": 100}`: a quantile
    of a series of in-run readings."""
    series = _get(readings, spec["path"])
    if not series or len(series) < spec.get("min_count", 1):
        return None
    return loadgen.quantile(series, spec["q"])


def share(spec, readings, ctx):
    """`{"path": "stats.phase_seconds", "part": "prefill"}`: one entry of a
    dict of seconds as a percentage of their sum."""
    parts = _get(readings, spec["path"])
    if not parts or not sum(parts.values()):
        return None
    return 100.0 * parts.get(spec["part"], 0.0) / sum(parts.values())


def ratio(spec, readings, ctx):
    """`{"num": path, "den": path, "scale": 100}`."""
    num, den = _get(readings, spec["num"]), _get(readings, spec["den"])
    if num is None or not den:
        return None
    return spec.get("scale", 1) * num / den


def mfu(spec, readings, ctx):
    """`{"path": "host.<a tokens/s/chip>"}`: operations the model needs per
    token (the configuration's opcount module) times that rate, over the
    chip's published bf16 peak."""
    rate = _get(readings, spec["path"])
    if rate is None:
        return None
    flops = _opcount(ctx).train_flops_per_token(
        ctx["model"], ctx["traffic"]["seq"])
    return 100.0 * rate * flops / peaks.peaks(
        ctx["device_kind"])["bf16_flops_per_s"]


def idle_share(spec, readings, ctx):
    t = readings.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def program_share(spec, readings, ctx):
    """`{"match": "^jit_decode"}`: device seconds of the jitted programs
    whose name matches, as a percentage of the traced window."""
    t = readings.get("trace")
    if not t:
        return None
    rx = re.compile(spec["match"])
    hit = [m["total_s"] for name, m in t["modules"].items() if rx.search(name)]
    return 100.0 * sum(hit) / t["window_s"] if hit else None


def kernel_roofline(spec, readings, ctx):
    """`{"trace_query": {"op": regex}, "opcount": "flash_fwd",
    "events_per_call": 1}`: the bound the opcount module gives for one call at the
    shape the trace shows ([b, h, s, d] of the kernel's output, so a chip's
    share under sharding), times the calls, over the kernel's device time."""
    q = _traced(readings, ctx)
    if not q:
        return None
    b, h, s, d = q["dims"][:4]
    kv_ratio = ctx["model"]["n_kv_heads"] / ctx["model"]["n_heads"]
    opcount = _opcount(ctx)
    ops, nbytes = getattr(opcount, spec["opcount"])(b, h, s, d, kv_ratio)
    bound = opcount.bound_seconds(ops, nbytes, peaks.peaks(ctx["device_kind"]))
    calls = q["count"] / spec.get("events_per_call", 1)
    return 100.0 * calls * bound / q["total_s"]


def decode_step_roofline(spec, readings, ctx):
    """Weight bytes over the chip's HBM bandwidth, over the median device
    time of one decode step. WEIGHTS ONLY: the KV the step also has to read
    is not in the bound, so this understates how near the step is to what
    the memory system allows."""
    q = _traced(readings, ctx)
    if not q:
        return None
    bound = _opcount(ctx).weight_bytes(ctx["model"]) / peaks.peaks(
        ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * bound / q["per_step_median_s"]
