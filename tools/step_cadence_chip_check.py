#!/usr/bin/env python3
"""python3 tools/step_cadence_chip_check.py --workload <cell> --seed <n>
        [--stop-s 1.5]

An induced stall, from outside: runs `benchmarks/run.py` itself, untouched,
as a child (`--trace 0`, BENCHMARK.json's `run_seconds`), stops the process
that holds the chip (`SIGSTOP`, every thread of it, as a frozen process is)
for `--stop-s` seconds half a minute after it opened the chip, and prints one
JSON object: the child's result line and the warning lines its stderr
carried, the benchmark parent's own (the stall record that came home on
`finish()`, `device_profiler.merge`) apart from the worker's. A frozen run
names itself on the benchmark parent's stderr (PR 52).

This process never imports jax. The exit code is `run.py`'s.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STOP_AFTER_S = 30.0   # of holding the chip: set-up is over, the steps run


def chip_holder(pids):
    """The pid among `pids` that has a chip open (`/dev/vfio/<n>`)."""
    for pid in pids:
        try:
            for fd in os.listdir(f"/proc/{pid}/fd"):
                link = os.readlink(f"/proc/{pid}/fd/{fd}")
                if link.startswith("/dev/vfio/") and link[10:].isdigit():
                    return pid
        except OSError:
            continue
    return None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--stop-s", type=float, default=1.5)
    args = ap.parse_args()

    sys.path.insert(0, ROOT)
    from benchmarks import run as bench_run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    err_path = os.path.join(ROOT, "chiprun_out",
                            f"outside-{args.workload}-{args.seed}.err")
    os.makedirs(os.path.dirname(err_path), exist_ok=True)
    with open(err_path, "wb") as err:
        child = subprocess.Popen(
            [sys.executable, *bench["command"][1:],
             "--workload", args.workload, "--seed", str(args.seed),
             "--seconds", str(bench["run_seconds"]), "--trace", "0"],
            cwd=ROOT, stdout=subprocess.PIPE, stderr=err)
        holder = None
        while child.poll() is None and holder is None:
            time.sleep(0.5)
            holder = chip_holder(bench_run.descendants())
        stopped = None
        if holder is not None:
            deadline = time.monotonic() + STOP_AFTER_S
            while child.poll() is None and time.monotonic() < deadline:
                time.sleep(0.1)
            if child.poll() is None:
                os.kill(holder, signal.SIGSTOP)
                time.sleep(args.stop_s)
                os.kill(holder, signal.SIGCONT)
                stopped = holder
        out, _ = child.communicate()
    with open(err_path, errors="replace") as f:
        lines = [ln.rstrip() for ln in f if " stalled: " in ln]
    last = out.decode(errors="replace").strip().splitlines()[-1:]
    try:
        result = json.loads(last[0])
    except (IndexError, ValueError):
        result = last
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "stopped_pid": stopped, "stop_s": args.stop_s, "rc": child.returncode,
        "result_line": result,
        "parents_own_lines": [ln for ln in lines
                              if not ln.startswith("(worker ")],
        "workers_lines": [ln for ln in lines if ln.startswith("(worker ")],
    }))
    return child.returncode


if __name__ == "__main__":
    sys.exit(main())
