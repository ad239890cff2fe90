"""tools/perf_gate.py (ISSUE 15): noise-banded regression thresholds
over the bench trajectory — threshold flips on fixture trajectories, the
seeded synthetic-regression gate."""

import json

import pytest

from tools import perf_gate
from tools.perf_gate import (
    GATED,
    UNTRACKED,
    append_history,
    evaluate,
    flatten_result,
    load_trajectory,
)

pytestmark = pytest.mark.profiling


def _row(**kw):
    base = {"_platform": "tpu", "_model_params_m": 1352.7,
            "_seq_len": 2048}
    base.update(kw)
    return base


# --------------------------------------------------------- threshold flips

def test_higher_better_flip():
    hist = [_row(mfu=0.65), _row(mfu=0.66), _row(mfu=0.655)]
    ok = evaluate(hist, _row(mfu=0.64))
    assert ok["ok"]
    bad = evaluate(hist, _row(mfu=0.40))  # -39% past the 8% band
    assert not bad["ok"]
    f = next(x for x in bad["findings"] if x["metric"] == "mfu")
    assert f["regression"] and f["baseline"] == pytest.approx(0.655)


def test_lower_better_flip():
    hist = [_row(serve_http_p99_ms=3.4), _row(serve_http_p99_ms=3.4)]
    assert evaluate(hist, _row(serve_http_p99_ms=3.6))["ok"]
    r = evaluate(hist, _row(serve_http_p99_ms=4.7))  # the r05 shape
    assert not r["ok"]


def test_smoke_bands_are_looser():
    hist = [_row(serve_http_p99_ms=3.4), _row(serve_http_p99_ms=3.4)]
    cur = _row(serve_http_p99_ms=4.7)
    assert not evaluate(hist, cur, smoke=False)["ok"]   # strict catches
    assert evaluate(hist, cur, smoke=True)["ok"]        # CI-host band


def test_improvements_pass():
    hist = [_row(engine_decode_tokens_per_sec=80.0),
            _row(engine_decode_tokens_per_sec=90.0)]
    r = evaluate(hist, _row(engine_decode_tokens_per_sec=1500.0))
    assert r["ok"]


def test_short_trajectory_skips():
    r = evaluate([_row(mfu=0.65)], _row(mfu=0.1))
    assert r["ok"]
    assert any(s["metric"] == "mfu" for s in r["skipped"])


def test_device_metric_context_matching():
    """A CPU smoke-fallback run (the r04 shape: mfu 0.0249) must not
    drag the TPU baseline — device metrics only compare like-for-like."""
    hist = [_row(mfu=0.65), _row(mfu=0.66),
            {"_platform": "cpu", "_model_params_m": 0.5, "_seq_len": 128,
             "mfu": 0.0249}]
    r = evaluate(hist, _row(mfu=0.64))
    f = next(x for x in r["findings"] if x["metric"] == "mfu")
    assert f["n_history"] == 2          # the cpu row was excluded
    assert f["baseline"] == pytest.approx(0.655)
    # and the cpu row compared against cpu history only
    cpu_hist = hist + [{"_platform": "cpu", "_model_params_m": 0.5,
                        "_seq_len": 128, "mfu": 0.025}]
    r = evaluate(cpu_hist, {"_platform": "cpu", "_model_params_m": 0.5,
                            "_seq_len": 128, "mfu": 0.024})
    f = next(x for x in r["findings"] if x["metric"] == "mfu")
    # baseline = median(0.0249, 0.025), reported rounded to 4 places
    assert f["n_history"] == 2
    assert f["baseline"] == pytest.approx(0.02495, abs=6e-5)


def test_abs_floor_suppresses_tiny_denominator_flips():
    # input_wait_frac 0.004 -> 0.02 is a 5x "regression" of nothing:
    # below the 0.05 absolute floor it must not trip
    hist = [_row(input_wait_frac=0.004), _row(input_wait_frac=0.004)]
    assert evaluate(hist, _row(input_wait_frac=0.02))["ok"]
    # a real input-starvation (0.3 of the step) trips
    assert not evaluate(hist, _row(input_wait_frac=0.30))["ok"]


# --------------------------------------------------------- flatten/history

def test_flatten_result_shapes():
    row = flatten_result({
        "metric": "llama_train_tokens_per_sec_per_chip", "value": 100.0,
        "vs_baseline": 1.6,
        "detail": {"mfu": 0.65, "platform": "tpu", "model_params_m": 10.0,
                   "seq_len": 128,
                   "engine_decode": {"roofline_frac": 0.85},
                   "object_put_gbps": {"numpy": 5.2, "jax": 10.0},
                   "ok": True},
    })
    assert row["llama_train_tokens_per_sec_per_chip"] == 100.0
    assert row["mfu"] == 0.65
    assert row["engine_decode.roofline_frac"] == 0.85
    assert row["object_put_gbps.jax"] == 10.0
    assert row["_platform"] == "tpu"
    assert "ok" not in row  # bools are not metrics


def test_append_history_roundtrip(tmp_path):
    hist = str(tmp_path / "BENCH_HISTORY.jsonl")
    result = {"metric": "llama_train_tokens_per_sec_per_chip",
              "value": 15000.0,
              "detail": {"mfu": 0.65, "platform": "tpu",
                         "model_params_m": 1352.7, "seq_len": 2048}}
    append_history(result, path=hist)
    append_history(result, path=hist)
    rows = load_trajectory(str(tmp_path), history_file=hist)
    assert len(rows) == 2
    assert rows[0]["mfu"] == 0.65
    assert "_ts" in rows[0]
    # the history rows feed the gate directly
    r = evaluate(rows, flatten_result(result))
    assert r["ok"]


# --------------------------------------------------------- the gate CLI

def _write_bench(path, n, value, mfu, p99):
    doc = {"n": n, "rc": 0, "parsed": {
        "metric": "llama_train_tokens_per_sec_per_chip", "value": value,
        "unit": "tokens/s/chip", "vs_baseline": round(mfu / 0.4, 3),
        "detail": {"mfu": mfu, "platform": "tpu",
                   "model_params_m": 1352.7, "seq_len": 2048,
                   "serve_http_p99_ms": p99}}}
    with open(path, "w") as f:
        json.dump(doc, f)


def test_seeded_synthetic_regression_fails_gate(tmp_path):
    """The acceptance fixture: a fabricated trajectory with a collapsed
    final run must exit nonzero — in strict AND smoke calibration."""
    for i, (v, mfu, p99) in enumerate(
            [(15000, 0.65, 3.4), (15100, 0.66, 3.3), (15050, 0.655, 3.5)],
            start=1):
        _write_bench(tmp_path / f"BENCH_r{i:02d}.json", i, v, mfu, p99)
    # the regressed run: half the throughput, 4x the p99
    _write_bench(tmp_path / "BENCH_r04.json", 4, 7000, 0.30, 14.0)
    assert perf_gate.main(["--root", str(tmp_path)]) == 1
    assert perf_gate.main(["--root", str(tmp_path), "--smoke"]) == 1


def test_healthy_synthetic_trajectory_passes(tmp_path):
    for i, (v, mfu, p99) in enumerate(
            [(15000, 0.65, 3.4), (15100, 0.66, 3.3), (15050, 0.655, 3.5),
             (15040, 0.654, 3.45)], start=1):
        _write_bench(tmp_path / f"BENCH_r{i:02d}.json", i, v, mfu, p99)
    assert perf_gate.main(["--root", str(tmp_path)]) == 0


def test_current_artifact_excluded_from_its_own_baseline(tmp_path):
    """`--current BENCH_rNN.json` on an artifact already in the
    trajectory must give the SAME verdict as gating it as the newest
    row — the run's own regression cannot sit in its baseline median."""
    for i, (v, mfu, p99) in enumerate(
            [(15000, 0.65, 3.4), (15100, 0.66, 3.4), (15050, 0.655, 3.4)],
            start=1):
        _write_bench(tmp_path / f"BENCH_r{i:02d}.json", i, v, mfu, p99)
    _write_bench(tmp_path / "BENCH_r04.json", 4, 15040, 0.654, 4.7)
    # default path (rows[-1] vs rows[:-1]) flags the p99 jump...
    assert perf_gate.main(["--root", str(tmp_path)]) == 1
    # ...and so does --current pointing at the same checked-in artifact
    assert perf_gate.main(
        ["--root", str(tmp_path),
         "--current", str(tmp_path / "BENCH_r04.json")]) == 1


def test_gate_with_explicit_current_file(tmp_path):
    for i, (v, mfu, p99) in enumerate(
            [(15000, 0.65, 3.4), (15100, 0.66, 3.3)], start=1):
        _write_bench(tmp_path / f"BENCH_r{i:02d}.json", i, v, mfu, p99)
    cur = tmp_path / "current.json"
    _write_bench(cur, 3, 14980, 0.653, 3.5)
    assert perf_gate.main(["--root", str(tmp_path),
                           "--current", str(cur)]) == 0
    _write_bench(cur, 3, 6000, 0.26, 3.5)
    assert perf_gate.main(["--root", str(tmp_path),
                           "--current", str(cur)]) == 1


# --------------------------------------------------------- coverage rule

def test_policy_table_sane():
    for key, pol in GATED.items():
        assert pol["direction"] in ("higher", "lower"), key
        assert 0 < pol["noise"] <= pol["smoke_noise"], (
            f"{key}: smoke band must be >= strict band")


def test_untracked_globs_do_not_swallow_gated_keys():
    """A gated metric must never also match an UNTRACKED glob in a way
    that would let a future edit silently drop its policy: GATED wins by
    construction (policy_for is checked first), but overlapping entries
    are a maintenance trap — keep them disjoint."""
    import fnmatch

    overlaps = [(k, pat) for k in GATED for pat in UNTRACKED
                if fnmatch.fnmatch(k, pat)]
    assert overlaps == [], overlaps
