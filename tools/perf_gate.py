"""CI perf-regression gate over the benchmark trajectory (ISSUE 15).

The r05 HTTP p99 regression (3.39 -> 4.69 ms) shipped because nothing
read the bench trajectory — a reviewer had to notice a number in a JSON
artifact. This gate makes the machine notice: it loads every historical
bench row (BENCH_r*.json artifacts + BENCH_HISTORY.jsonl lines), treats
the newest row (or --current) as the run under test, and fails CI when a
gated metric falls past its per-metric noise band versus the median of
its history.

It has NO PRODUCER any more: the root bench script that wrote those rows
was deleted (PR 29) and no tool runs this gate. The repo's benchmark is
`BENCHMARK.json` + `benchmarks/`, its record `PERF_LEDGER.jsonl`; this
file and tests/test_perf_gate.py are named debt (ROADMAP, D3 remainder).

Two calibrations, because shared CI hosts are loud:

* strict (default) — bands sized for a quiet, dedicated host; this is
  the mode that catches an r05-class p99 drift (+38%).
* --smoke — loose bands for the shared 1-core CI host where serve/rllib
  numbers can legitimately swing 2x run to run; still catches collapse-
  class regressions (half the throughput, double the latency).

DEVICE metrics (MFU, tokens/s/chip, decode, roofline) only compare
against history rows from the SAME platform and model shape — a CPU
smoke-fallback run (r04) must not drag the TPU baseline, and vice versa.
Host-side subsystem metrics (serve/rllib/dataplane, which always run in
CPU subprocesses) compare across all rows.

Coverage contract (CONTRIBUTING): every numeric key a bench run emits is
either GATED here or explicitly listed in UNTRACKED — enforced by a
fixture test (tests/test_perf_gate.py) so a new bench metric cannot ship
without declaring its regression policy.

Usage:
    python -m tools.perf_gate                 # gate newest row, strict
    python -m tools.perf_gate --smoke         # loose bands, shared hosts
    python -m tools.perf_gate --current f.json  # gate an explicit run
    python -m tools.perf_gate --list-metrics  # show policies + trajectory
"""

from __future__ import annotations

import argparse
import fnmatch
import glob as _glob
import json
import os
import statistics
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HISTORY_FILE = "BENCH_HISTORY.jsonl"
BENCH_GLOB = "BENCH_r*.json"

# Context keys (underscore-prefixed in flattened rows; never gated).
_CONTEXT_KEYS = ("_ts", "_run", "_platform", "_model_params_m", "_seq_len")

# metric -> policy. direction: "higher" is better / "lower" is better.
# noise / smoke_noise: fractional band around the history median.
# device: True -> only compare rows with matching platform+model context.
GATED: Dict[str, Dict[str, Any]] = {
    "llama_train_tokens_per_sec_per_chip": {
        "direction": "higher", "noise": 0.10, "smoke_noise": 0.35,
        "device": True},
    "mfu": {"direction": "higher", "noise": 0.08, "smoke_noise": 0.30,
            "device": True},
    "engine_decode_tokens_per_sec": {
        "direction": "higher", "noise": 0.15, "smoke_noise": 0.45,
        "device": True},
    "engine_decode.roofline_frac": {
        "direction": "higher", "noise": 0.10, "smoke_noise": 0.35,
        "device": True},
    "engine_decode.on_device_tokens_per_sec": {
        "direction": "higher", "noise": 0.15, "smoke_noise": 0.45,
        "device": True},
    "train_multichip_tokens_per_sec_per_chip": {
        "direction": "higher", "noise": 0.20, "smoke_noise": 0.50,
        "device": True},
    "train_scaling_efficiency": {
        "direction": "higher", "noise": 0.15, "smoke_noise": 0.45,
        "device": True},
    # device-phase attribution (ISSUE 15): a step that starts waiting on
    # input is a regression even when throughput noise hides it
    "input_wait_frac": {
        "direction": "lower", "noise": 0.50, "smoke_noise": 1.50,
        "device": True, "abs_floor": 0.05},
    "device_frac": {
        "direction": "higher", "noise": 0.25, "smoke_noise": 0.60,
        "device": True},
    "compile_s": {
        "direction": "lower", "noise": 1.00, "smoke_noise": 3.00,
        "device": True, "abs_floor": 5.0},
    # host-side subsystems (always CPU subprocesses)
    "rllib_env_steps_per_sec": {
        "direction": "higher", "noise": 0.30, "smoke_noise": 0.60},
    "rllib_decoupled_env_steps_per_sec": {
        "direction": "higher", "noise": 0.30, "smoke_noise": 0.60},
    "serve_http_rps": {
        "direction": "higher", "noise": 0.30, "smoke_noise": 0.60},
    "serve_handle_rps": {
        "direction": "higher", "noise": 0.30, "smoke_noise": 0.60},
    "serve_http_p50_ms": {
        "direction": "lower", "noise": 0.40, "smoke_noise": 1.00},
    "serve_http_p99_ms": {
        "direction": "lower", "noise": 0.30, "smoke_noise": 1.00},
    "serve_http_sustained_rps": {
        "direction": "higher", "noise": 0.30, "smoke_noise": 0.60},
    "serve_http_sustained_p99_ms": {
        "direction": "lower", "noise": 0.40, "smoke_noise": 1.00},
    "object_put_gbps.numpy": {
        "direction": "higher", "noise": 0.30, "smoke_noise": 0.70},
    "object_put_gbps.jax": {
        "direction": "higher", "noise": 0.30, "smoke_noise": 0.70},
    "object_get_gbps.numpy": {
        "direction": "higher", "noise": 0.40, "smoke_noise": 0.80},
    "object_get_gbps.jax": {
        "direction": "higher", "noise": 0.40, "smoke_noise": 0.80},
    "input_pipeline_overlap_frac": {
        "direction": "higher", "noise": 0.50, "smoke_noise": 0.90},
    "llm_prefix_ttft_cold_ms": {
        "direction": "lower", "noise": 0.40, "smoke_noise": 1.00},
    "llm_prefix_ttft_hit_ms": {
        "direction": "lower", "noise": 0.40, "smoke_noise": 1.00},
    "llm_serving_ttft_p50_ms": {
        "direction": "lower", "noise": 0.40, "smoke_noise": 1.00},
    "llm_serving_ttft_p99_ms": {
        "direction": "lower", "noise": 0.50, "smoke_noise": 1.20},
    "llm_serving_tokens_per_sec": {
        "direction": "higher", "noise": 0.30, "smoke_noise": 0.60},
}

# Numeric bench keys that are CONTEXT, not perf: dimensions, counts,
# configuration echoes, per-run detail blobs. Globs; reviewed by the
# coverage fixture test — adding a bench metric means deciding, here or
# in GATED, what it is.
UNTRACKED: Tuple[str, ...] = (
    "vs_baseline",              # derived from mfu (gated above)
    "step_time_ms",             # inverse of the gated tokens/s
    "model_params_m", "seq_len", "global_batch", "loss", "n_devices",
    "model_proxy.*", "engine_model_params_m",
    "engine_decode.model_params_m", "engine_decode.max_batch",
    "engine_decode.new_tokens_per_req", "engine_decode.dispatch_rt_ms",
    "engine_decode.n_dispatches",
    "engine_decode.hbm_roofline_tokens_per_sec",   # config-derived bound
    "train_step_phases.*",      # full report; headline fracs gated above
    "hbm.*",                    # occupancy snapshot, not a perf scalar
    "train_multichip_detail.*",
    "rllib_env_steps_detail.*", "rllib_decoupled_detail.*",
    "rllib_decoupled_scaling",  # 1-core CI host time-slices the fleet
    "serve_http_sustained_detail.*", "llm_prefix_ttft_detail.*",
    "llm_serving_detail.*", "dataplane_detail.*",
)


def flatten_result(result: Dict[str, Any]) -> Dict[str, Any]:
    """One bench result (a {"metric", "value", "detail"} object, or a
    BENCH_r*.json 'parsed' field) -> a flat metric->value row. The headline rides under
    its metric name; detail keys flatten with dotted paths; context keys
    get an underscore prefix so the gate never mistakes them for perf."""
    row: Dict[str, Any] = {}
    metric = result.get("metric")
    if metric and isinstance(result.get("value"), (int, float)):
        row[metric] = float(result["value"])
    if isinstance(result.get("vs_baseline"), (int, float)):
        row["vs_baseline"] = float(result["vs_baseline"])
    detail = result.get("detail") or {}
    row["_platform"] = detail.get("platform")
    row["_model_params_m"] = detail.get("model_params_m")
    row["_seq_len"] = detail.get("seq_len")

    def walk(obj, prefix):
        for k, v in obj.items():
            path = f"{prefix}.{k}" if prefix else str(k)
            if isinstance(v, bool):
                continue
            if isinstance(v, (int, float)):
                row[path] = float(v)
            elif isinstance(v, dict):
                walk(v, path)

    walk(detail, "")
    return row


def policy_for(key: str) -> Optional[Dict[str, Any]]:
    return GATED.get(key)


def is_untracked(key: str) -> bool:
    if key.startswith("_") or key.endswith(("_error", ".error", "_note")):
        return True
    return any(fnmatch.fnmatch(key, pat) for pat in UNTRACKED)


def uncovered_keys(row: Dict[str, Any]) -> List[str]:
    """Numeric keys of a bench row with NO declared policy — the
    CONTRIBUTING 'every new bench metric registers a perf_gate threshold'
    rule; the fixture test asserts this is empty for the checked-in
    trajectory."""
    return sorted(
        k for k, v in row.items()
        if isinstance(v, float) and policy_for(k) is None
        and not is_untracked(k))


# ------------------------------------------------------------- trajectory

def _bench_artifact_row(path: str) -> Optional[Dict[str, Any]]:
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    parsed = doc.get("parsed") if isinstance(doc, dict) else None
    if not isinstance(parsed, dict) or "metric" not in parsed:
        return None
    row = flatten_result(parsed)
    row["_run"] = os.path.basename(path)
    return row


def load_trajectory(root: str = REPO_ROOT,
                    history_file: Optional[str] = None,
                    bench_glob: Optional[str] = None) -> List[Dict[str, Any]]:
    """All known bench rows, oldest first: BENCH_r*.json artifacts, then
    BENCH_HISTORY.jsonl lines (the machine-readable trajectory,
    already flattened)."""
    rows: List[Dict[str, Any]] = []
    for path in sorted(_glob.glob(
            os.path.join(root, bench_glob or BENCH_GLOB))):
        row = _bench_artifact_row(path)
        if row:
            rows.append(row)
    hist = history_file or os.path.join(root, HISTORY_FILE)
    if os.path.exists(hist):
        with open(hist) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                if isinstance(row, dict):
                    rows.append(row)
    return rows


def append_history(result: Dict[str, Any],
                   path: Optional[str] = None) -> Dict[str, Any]:
    """Append one flattened metric->value JSON line for this bench run,
    so the gate reads a machine-readable trajectory instead of parsing
    BENCH_r*.json tails."""
    row = flatten_result(result)
    row["_ts"] = round(time.time(), 3)
    path = path or os.path.join(REPO_ROOT, HISTORY_FILE)
    with open(path, "a") as f:
        f.write(json.dumps(row, sort_keys=True) + "\n")
    return row


# -------------------------------------------------------------- the gate

def _context_match(a: Dict[str, Any], b: Dict[str, Any]) -> bool:
    """Device metrics only compare like-for-like runs: same platform and
    model shape (a CPU smoke fallback must not drag a TPU baseline)."""
    return (a.get("_platform") == b.get("_platform")
            and a.get("_model_params_m") == b.get("_model_params_m")
            and a.get("_seq_len") == b.get("_seq_len"))


def evaluate(history: List[Dict[str, Any]], current: Dict[str, Any],
             smoke: bool = False, min_history: int = 2
             ) -> Dict[str, Any]:
    """Judge `current` against `history` (which must NOT include it).
    Returns {"ok": bool, "findings": [...], "skipped": [...]}: one
    finding per gated metric with enough trajectory, regression=True
    where it fell past its band."""
    findings: List[Dict[str, Any]] = []
    skipped: List[Dict[str, str]] = []
    for key, pol in GATED.items():
        cur = current.get(key)
        if not isinstance(cur, (int, float)):
            continue
        rows = history
        if pol.get("device"):
            rows = [r for r in history if _context_match(r, current)]
        vals = [r[key] for r in rows
                if isinstance(r.get(key), (int, float))]
        if len(vals) < min_history:
            skipped.append({"metric": key,
                            "reason": f"trajectory too short "
                                      f"({len(vals)} < {min_history})"})
            continue
        baseline = statistics.median(vals)
        band = pol["smoke_noise"] if smoke else pol["noise"]
        if pol["direction"] == "higher":
            limit = baseline * (1.0 - band)
            regression = cur < limit
        else:
            limit = baseline * (1.0 + band)
            # an absolute floor keeps tiny-denominator metrics (an 0.01
            # input_wait_frac, a 2s compile) from tripping on jitter
            floor = pol.get("abs_floor")
            regression = cur > limit and (floor is None or cur > floor)
        findings.append({
            "metric": key, "baseline": round(baseline, 4),
            "current": round(float(cur), 4), "band": band,
            "limit": round(limit, 4), "n_history": len(vals),
            "direction": pol["direction"], "regression": bool(regression),
        })
    regressions = [f for f in findings if f["regression"]]
    for f in regressions:
        try:  # best-effort: a CI process has no sink, the record is local
            from ray_tpu._private.event_log import emit

            emit("perf.regression", metric=f["metric"],
                 baseline=f["baseline"], current=f["current"],
                 band=f["band"])
        except Exception:  # noqa: BLE001 — the exit code is the gate
            pass
    return {"ok": not regressions, "findings": findings,
            "skipped": skipped, "regressions": len(regressions)}


def _format_report(report: Dict[str, Any], smoke: bool) -> str:
    mode = "smoke (loose bands, shared CI host)" if smoke \
        else "strict (quiet-host bands)"
    lines = [f"perf gate [{mode}]"]
    hdr = (f"  {'metric':<40} {'baseline':>10} {'current':>10} "
           f"{'limit':>10} {'band':>6}  verdict")
    lines.append(hdr)
    for f in sorted(report["findings"],
                    key=lambda f: (not f["regression"], f["metric"])):
        verdict = "REGRESSION" if f["regression"] else "ok"
        lines.append(
            f"  {f['metric']:<40} {f['baseline']:>10.3f} "
            f"{f['current']:>10.3f} {f['limit']:>10.3f} "
            f"{f['band']:>6.2f}  {verdict}")
    for s in report["skipped"]:
        lines.append(f"  {s['metric']:<40} skipped: {s['reason']}")
    lines.append(f"  => {'PASS' if report['ok'] else 'FAIL'} "
                 f"({report['regressions']} regression(s), "
                 f"{len(report['findings'])} gated, "
                 f"{len(report['skipped'])} skipped)")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="perf_gate",
        description="fail CI when a bench metric regresses past its "
                    "noise band vs the BENCH_* trajectory")
    ap.add_argument("--root", default=REPO_ROOT,
                    help="repo root holding BENCH_r*.json / the history")
    ap.add_argument("--history", help=f"history file (default "
                                      f"<root>/{HISTORY_FILE})")
    ap.add_argument("--current",
                    help="bench result JSON to gate (a result object "
                         "or a BENCH_r*.json artifact); default: "
                         "the newest trajectory row")
    ap.add_argument("--smoke", action="store_true",
                    help="loose noise bands for shared CI hosts (strict "
                         "bands assume a quiet dedicated host)")
    ap.add_argument("--min-history", type=int, default=2)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--list-metrics", action="store_true",
                    help="print the policy table and trajectory "
                         "coverage, then exit 0")
    args = ap.parse_args(argv)

    rows = load_trajectory(args.root, history_file=args.history)
    if args.list_metrics:
        for key, pol in sorted(GATED.items()):
            n = sum(1 for r in rows
                    if isinstance(r.get(key), (int, float)))
            print(f"{key:<44} {pol['direction']:<7} "
                  f"band={pol['noise']:.2f}/{pol['smoke_noise']:.2f} "
                  f"history={n}")
        return 0
    if args.current:
        with open(args.current) as f:
            doc = json.load(f)
        if "parsed" in doc and isinstance(doc["parsed"], dict):
            doc = doc["parsed"]
        current = flatten_result(doc) if "metric" in doc else doc
        # a --current that is itself a trajectory artifact must not sit
        # in its own baseline (the run's regression would drag the
        # median toward itself and loosen the band)
        cur_base = os.path.basename(args.current)
        history = [r for r in rows if r.get("_run") != cur_base]
    else:
        if not rows:
            print("perf gate: no bench trajectory found (no "
                  f"{BENCH_GLOB} or {HISTORY_FILE} under {args.root})",
                  file=sys.stderr)
            return 2
        current, history = rows[-1], rows[:-1]
    report = evaluate(history, current, smoke=args.smoke,
                      min_history=args.min_history)
    unknown = uncovered_keys(current)
    if unknown:
        print("perf gate: bench metrics with NO declared policy "
              "(add to GATED or UNTRACKED in tools/perf_gate.py): "
              + ", ".join(unknown), file=sys.stderr)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        print(_format_report(report, args.smoke))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
