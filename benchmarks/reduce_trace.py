"""From a `jax.profiler` xplane to device busy/idle, time per jitted
program and per op, and the longest idle gaps by what the host was doing.

Layout of a TPU xplane as this reads it (looked at by hand on a v5e trace,
jax 0.9): one plane per chip named `/device:TPU:<n>` whose line
`XLA Modules` has one event per execution of a jitted program
(`jit_<fn>(<fingerprint>)`) and whose line `XLA Ops` has one event per HLO
op executed, named by its HLO text (`%fusion.12 = bf16[4,2048]{..} fusion(`
...), control flow (`while`, `conditional`, `call`) nested around its body's
ops. The plane `/host:CPU` has one line per thread; with the python tracer
on, the line `python` holds every python call (`$file.py:123 fn`).

Runs in the process that took the trace (it already holds jax); the
parent of the benchmark never imports jax. Times are seconds.
"""

from __future__ import annotations

import re
import statistics
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
_OP = re.compile(r"^%([A-Za-z_\-]+)[\w.\-]* = \(?(\w+)\[([\d,]*)\]")
_MODULE = re.compile(r"^(.*?)\(\d+\)$")
GAP_FLOOR_S = 20e-6   # shorter gaps are the device's own op-to-op latency
LABELLED_GAPS = 64    # the longest gaps get a host label each


def short_op(hlo: str) -> str:
    """`%fusion.12 = bf16[4,2048]{...} fusion(...)` -> `fusion_bf16_4_2048`;
    a Pallas kernel (`tpu_custom_call`) gets the prefix `pallas_`."""
    m = _OP.match(hlo)
    if not m:
        return re.sub(r"[^\w.\-]+", "_", hlo[:48])
    name = "_".join(x for x in (m[1], m[2], m[3].replace(",", "_")) if x)
    return ("pallas_" + name) if "tpu_custom_call" in hlo else name


def module_name(name: str) -> str:
    m = _MODULE.match(name)
    return m[1] if m else name


def _events(line):
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for e in line.events]


def union(intervals):
    """Sorted, merged copy of [(start, end), ...]."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def self_times(events):
    """Per event, its duration minus what events nested inside it cover
    (a `while` is charged only what its body's ops leave)."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    selfs = [e[1] - e[0] for e in events]
    stack = []
    for i, (a, b, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= b - a
        stack.append(i)
    return events, selfs


def _host_label(host_lines, a, b):
    """Innermost host event that covers the middle of the gap [a, b]."""
    mid, best = (a + b) / 2, None
    for events in host_lines:
        for s, e, name in events:
            if s <= mid <= e and (best is None or e - s < best[0]):
                best = (e - s, name)
    return None if best is None else "host:" + best[1][:80]


def _plane(plane, queries):
    lines = {ln.name: ln for ln in plane.lines}
    ops = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
    modules = sorted(_events(lines["XLA Modules"])) \
        if "XLA Modules" in lines else []
    busy = union([(a, b) for a, b, _ in ops] or
                 [(a, b) for a, b, _ in modules])
    if not busy:
        return None
    out = {"t0": busy[0][0], "t1": busy[-1][1], "busy": busy,
           "busy_s": sum(b - a for a, b in busy), "module_events": modules}
    per_module = defaultdict(list)
    for a, b, name in modules:
        per_module[module_name(name)].append(b - a)
    out["modules"] = {
        k: {"total_s": sum(v), "count": len(v),
            "median_s": statistics.median(v)} for k, v in per_module.items()}
    per_op = defaultdict(float)
    events, selfs = self_times(ops)
    for (_, _, name), s in zip(events, selfs):
        per_op[short_op(name)] += s
    out["ops"] = dict(per_op)
    out["queries"] = {k: _query(q, ops, modules) for k, q in queries.items()}
    return out


def _query(q, ops, modules):
    """`{"op": regex}` -> the matching op events: their seconds, their
    number and the dimensions of the first one's (first) output. `{"module": regex, "step_op": regex}`
    -> per execution of a matching program, its seconds divided by the
    number of `step_op` events inside it less one (the outer loop itself):
    the device time of one step of a fused multi-step program."""
    if "op" in q:
        rx = re.compile(q["op"])
        hits = [(b - a, name) for a, b, name in ops if rx.search(name)]
        if not hits:
            return None
        m = _OP.match(hits[0][1])
        return {"total_s": sum(s for s, _ in hits), "count": len(hits),
                "dims": [int(x) for x in m[3].split(",") if x] if m else []}
    rx, step = re.compile(q["module"]), re.compile(q["step_op"])
    starts = sorted((a, name) for a, _, name in ops if step.search(name))
    per_step = []
    for a, b, name in modules:
        if rx.search(module_name(name)):
            n = sum(1 for s, _ in starts if a <= s < b) - 1
            if n >= 1:
                per_step.append((b - a) / n)
    if not per_step:
        return None
    return {"per_step_median_s": statistics.median(per_step),
            "count": len(per_step)}


def reduce_xplane(path: str, queries: dict | None = None) -> dict | None:
    """-> None when no operation ran on a TPU in the trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = [p for p in data.planes if p.name.startswith(DEVICE_PLANE)]
    reduced = [r for r in (_plane(p, queries or {}) for p in planes) if r]
    if not reduced:
        return None
    t0 = min(r["t0"] for r in reduced)
    t1 = max(r["t1"] for r in reduced)
    first = reduced[0]
    host_lines = [_events(ln) for p in data.planes if p.name == "/host:CPU"
                  for ln in p.lines]
    gaps = [(b2 - b1, b1, b2) for (_, b1), (b2, _) in
            zip(first["busy"], first["busy"][1:]) if b2 - b1 >= GAP_FLOOR_S]
    gaps.sort(reverse=True)
    by_label = defaultdict(float)
    mods = first["module_events"]
    for n, (length, a, b) in enumerate(gaps):
        label = _host_label(host_lines, a, b) if n < LABELLED_GAPS else None
        if label is None and n < LABELLED_GAPS:
            before = [m for m in mods if m[1] <= a + 1e-9]
            after = [m for m in mods if m[0] >= b - 1e-9]
            label = "between:%s:%s" % (
                module_name(before[-1][2]) if before else "start",
                module_name(after[0][2]) if after else "end")
        by_label[label or "shorter_gaps"] += length
    ops = defaultdict(float)
    for r in reduced:
        for k, v in r["ops"].items():
            ops[k] += v / len(reduced)
    top = lambda d: sorted(([k, v] for k, v in d.items()),  # noqa: E731
                           key=lambda kv: -kv[1])[:10]
    return {
        "chips": len(reduced),
        "window_s": t1 - t0,
        "busy_s": sum(r["busy_s"] for r in reduced) / len(reduced),
        "modules": first["modules"],
        "device_ops": top(ops),
        "idle_gaps": top(by_label),
        "queries": first["queries"],
    }
