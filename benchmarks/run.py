#!/usr/bin/env python3
"""python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the machine it is started on and prints
one JSON object as the last line of its output (see PERF.md for the
contract). Everything about a cell is found by name: its entry in
`workloads`, `benchmarks/configs/<config>.json` (whose `kind` selects
`benchmarks/<kind>_cell.py`, and whose `program`, `reference` and `opcount`
name the model module and its config class, the plain reference and the
operation counts), `benchmarks/traffic/<traffic>.json`, and one
`benchmarks/metrics/<metric>.json` per metric. This parent never imports
jax: a chip belongs to the worker or replica the cell starts.

`--rehearse` runs the same command at the tiny sizes the configuration's
`rehearsal` group gives, on the CPU: it checks a cell's files and control
flow without chip time, prints what it counted under `"rehearsal"`, never
under a metric's name, and exits 3 (it is not a measurement).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import signal
import sys
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

def load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def descendants() -> set:
    """pids of every live process started, directly or not, by this one."""
    parent = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                parent[int(pid)] = int(f.read().rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
    mine, grew = {os.getpid()}, True
    while grew:
        more = {p for p, pp in parent.items() if pp in mine} - mine
        mine |= more
        grew = bool(more)
    return mine - {os.getpid()}


def wait_until_ended(pids: set, timeout_s: float = 120.0) -> None:
    """A worker that held a chip is killed when its lease ends, but the
    chip is free only once that process is gone: the next run's worker
    found /dev/vfio busy on the four-chip host. So wait for every process
    this run started, and kill what outlives the timeout."""
    def alive(pid):
        try:
            with open(f"/proc/{pid}/stat") as f:
                return f.read().rsplit(")", 1)[1].split()[0] != "Z"
        except OSError:
            return False

    deadline = time.monotonic() + timeout_s
    while any(alive(p) for p in pids) and time.monotonic() < deadline:
        time.sleep(0.1)
    for p in pids:
        if alive(p):
            try:
                os.kill(p, signal.SIGKILL)
            except OSError:
                pass


def metric_value(name, spec, readings, ctx):
    module, fn = spec["reader"].rsplit(".", 1)
    reader = getattr(importlib.import_module("benchmarks." + module), fn)
    return reader(spec, readings, dict(ctx, name=name))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    os.chdir(ROOT)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [w for w in bench["workloads"] if w["name"] == args.workload]
    config = load("configs", cell["config"] + ".json")
    traffic = load("traffic", cell["traffic"] + ".json")
    if args.rehearse:
        config = merged(config, config["rehearsal"])
        traffic = merged(traffic, traffic.get("rehearsal", {}))
    wanted = [m for m in bench["per_layer" if args.trace else "end_to_end"]
              if args.workload in m.get("workloads", [args.workload])]
    specs = {m["name"]: load("metrics", m["name"] + ".json") for m in wanted}
    # the program's config fields: those read from the published keys,
    # then those the configuration sets itself
    program = config["program"]
    model = {field: config[key] for field, key in program["fields_from"].items()}
    model.update(program["fields"])

    out_dir = os.path.join(ROOT, ".bench_out", args.workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    # workers and replicas: import `benchmarks.*`, share one persistent
    # compile cache at a fixed path in this checkout (or where the machine
    # says), and keep even the smallest programs in it
    os.environ["PYTHONPATH"] = os.pathsep.join(
        p for p in (ROOT, os.environ.get("PYTHONPATH")) if p)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import compile_cache

    compile_cache.enable()
    ray_tpu.init()
    try:
        have = int(ray_tpu.cluster_resources().get("TPU", 0))
        if not args.rehearse and have < cell["chips"]:
            print(f"the node has {have} TPU chips, the cell needs "
                  f"{cell['chips']}", file=sys.stderr)
            return 2
        kind = importlib.import_module(f"benchmarks.{config['kind']}_cell")
        result = kind.run({
            "config": config, "traffic": traffic, "model": model,
            "chips": cell["chips"], "seed": args.seed,
            "seconds": args.seconds, "rehearse": args.rehearse,
            "out_dir": out_dir,
            "trace_dir": os.path.join(out_dir, "trace") if args.trace
            else None,
            "trace_queries": {k: s["trace_query"] for k, s in specs.items()
                              if "trace_query" in s},
        })
    finally:
        started = descendants()
        serve.shutdown()
        ray_tpu.shutdown()
        wait_until_ended(started)
        # traces and the trainer's storage: reduced already, not kept
        shutil.rmtree(out_dir, ignore_errors=True)
    if "jax" in sys.modules:
        print("the benchmark's parent imported jax", file=sys.stderr)
        return 2

    device = result["device"]
    ctx = {"chips": cell["chips"], "model": model, "seconds": args.seconds,
           "traffic": traffic, "opcount": config["opcount"],
           "device_kind": device["kind"],
           "setup_s": result["t_window_wall"] - T_START}
    readings = dict(result["readings"], device=device)
    units = {m["name"]: m["unit"] for m in wanted}
    if args.rehearse or device["platform"] != "tpu":
        # counts and control flow only: nothing here is a device number
        readable = {}
        for name, spec in specs.items():
            try:
                readable[name] = metric_value(
                    name, spec, readings, ctx) is not None
            except KeyError as e:  # no peaks for this device
                readable[name] = f"needs the chip: {e}"
        print(json.dumps({"rehearsal": {
            "correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "device": device,
            "checks": result["checks"],
            "counts": {k: v for group in ("stats", "host")
                       for k, v in (readings.get(group) or {}).items()
                       if isinstance(v, int)},
            "metric_was_readable": readable}}))
        print("not a measurement: no result line is printed off the TPU",
              file=sys.stderr)
        return 3
    values = {name: metric_value(name, spec, readings, ctx)
              for name, spec in specs.items()}
    line = {
        "correct": result["correct"], "attempted": result["attempted"],
        "failed": result["failed"],
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in values.items() if v is not None},
        "device": device,
        # beyond the contract's keys: how near the reference the program was
        "checks": result["checks"],
    }
    if device["count"] != cell["chips"]:
        print(f"the cell computed on {device['count']} chips, not "
              f"{cell['chips']}", file=sys.stderr)
        return 2
    trace = readings.get("trace")
    if args.trace:
        if not trace or trace["busy_s"] <= 0:
            print("no operation ran on the device in the traced window",
                  file=sys.stderr)
            return 2
        line["device"] = dict(device, busy_s=trace["busy_s"],
                              window_s=trace["window_s"])
        line["breakdown"] = {"device_ops": trace["device_ops"],
                             "idle_gaps": trace["idle_gaps"]}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
