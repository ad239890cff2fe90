"""Device-plane performance observability: the per-step phase records
(accounting, compile carve-out), compile telemetry, HBM export, and the
`ray-tpu profile --device` fan-out/chrome-merge — the `pytest -m
profiling` fast slice. The span layer itself: tests/test_spans.py."""

import json
import os
import time

import pytest

import ray_tpu
from ray_tpu._private import device_profiler as dp
from ray_tpu._private.device_profiler import (
    DeviceStepProfiler,
    get_profiler,
    hbm_stats,
    snapshot_all,
    span,
    steps_to_spans,
)

pytestmark = pytest.mark.profiling


# ------------------------------------------------- phase accounting math

def test_phase_accounting_on_canned_timings():
    prof = DeviceStepProfiler("canned")
    prof.record_step({"input_wait": 0.2, "h2d": 0.1,
                      "device_execute": 0.6, "reply": 0.1}, tokens=10)
    prof.record_step({"input_wait": 0.0, "device_execute": 1.0}, tokens=20)
    rep = prof.report(emit_event=False)
    assert rep["steps"] == 2
    acc = rep["accounted_s"]
    assert acc == pytest.approx(2.0, abs=1e-6)
    assert rep["input_wait_frac"] == pytest.approx(0.2 / 2.0, abs=1e-3)
    assert rep["device_execute_frac"] == pytest.approx(1.6 / 2.0, abs=1e-3)
    assert rep["h2d_frac"] == pytest.approx(0.05, abs=1e-3)
    assert rep["compile_s"] == 0.0
    # per-step records carry phases + tokens for the chrome export
    assert [r["tokens"] for r in rep["recent_steps"]] == [10, 20]


def test_compile_carveout_from_device_execute():
    prof = DeviceStepProfiler("carve")
    with span("t.carve.step") as sp:
        # a backend compile fires mid-phase (simulated listener hit)
        dp._on_event_duration(
            "/jax/core/compile/backend_compile_duration", 0.25)
        time.sleep(0.01)
    prof.record_step({"device_execute": sp.seconds})
    rep = prof.report(emit_event=False)
    phases = rep["phase_seconds"]
    assert phases["compile"] == pytest.approx(0.25, abs=1e-6)
    # the 0.25s carve exceeds the real ~10ms phase: floored at zero, so
    # the steady-state phase never wears the compile storm
    assert phases["device_execute"] >= 0.0
    assert rep["compile_s"] == pytest.approx(0.25, abs=1e-6)
    # the next step, with no compile since, keeps all of its phase
    prof.record_step({"device_execute": 0.5})
    assert prof.report(emit_event=False)["phase_seconds"][
        "device_execute"] == pytest.approx(0.5, abs=1e-6)


def test_record_step_exports_nothing_per_step(monkeypatch):
    """No histogram observe, no gauge set and no device sweep on the way
    of a step: the HBM gauges move when a report is asked for."""
    from ray_tpu.util import metrics as um

    touched = []
    monkeypatch.setattr(um.Histogram, "observe",
                        lambda self, *a, **k: touched.append("observe"))
    monkeypatch.setattr(um.Gauge, "set",
                        lambda self, *a, **k: touched.append("set"))
    monkeypatch.setattr(dp, "hbm_stats",
                        lambda *a, **k: touched.append("hbm") or {})
    prof = DeviceStepProfiler("quiet")
    for _ in range(20):
        prof.record_step({"input_wait": 0.01, "device_execute": 0.02})
    assert touched == []
    rep = prof.report(emit_event=False)
    assert touched == ["hbm"] and rep["steps"] == 20
    assert "mfu" not in rep


def test_report_fractions_cover_custom_phases():
    prof = DeviceStepProfiler("custom")
    prof.record_step({"prefill": 0.3, "device_execute": 0.6, "reply": 0.1})
    rep = prof.report(emit_event=False, include_hbm=False)
    assert rep["prefill_frac"] == pytest.approx(0.3, abs=1e-3)
    assert rep["device_execute_frac"] == pytest.approx(0.6, abs=1e-3)
    assert rep["hbm"] == {} and rep["h2d_frac"] == 0.0
    prof.reset()
    assert prof.report(emit_event=False, include_hbm=False)["steps"] == 0


# ------------------------------------------------- fencing correctness

def test_spanned_step_outputs_match_unspanned():
    """A span around a jitted step neither fences nor changes it: the
    outputs are bitwise those of the bare loop, and what the span timed
    ends where the caller fenced."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    f = jax.jit(lambda x: jnp.sin(x) @ x + 1.0)
    x0 = jnp.ones((64, 64))

    x = x0
    for _ in range(5):
        x = f(x)
    bare = jax.device_get(x)

    prof = DeviceStepProfiler("parity")
    x = x0
    for _ in range(5):
        with span("t.parity.step") as sp:
            x = f(x)
            jax.block_until_ready(x)
        prof.record_step({"device_execute": sp.seconds})
    assert np.array_equal(bare, jax.device_get(x))
    rep = prof.report(emit_event=False)
    assert rep["steps"] == 5
    assert rep["phase_seconds"]["device_execute"] > 0


def test_span_overhead_within_two_percent():
    """The acceptance bound: a spanned step's wall time within 2% of the
    bare step's on this host. min-of-interleaved-trials is the estimator —
    the minimum is robust to CI-host load spikes; both arms run the
    identical fenced loop, isolating the span's own cost."""
    import jax
    import jax.numpy as jnp

    # ~2 ms a step on this host, so the bound allows the span and
    # `record_step` some 40 us between them
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x0 = jnp.ones((768, 768))
    jax.block_until_ready(f(x0))  # compile outside both arms
    steps = 12

    def plain():
        x = x0
        out = []
        for _ in range(steps):
            t0 = time.perf_counter()
            x = f(x)
            jax.block_until_ready(x)
            out.append(time.perf_counter() - t0)
        return out

    prof = DeviceStepProfiler("overhead")

    def spanned():
        x = x0
        out = []
        for _ in range(steps):
            t0 = time.perf_counter()
            with span("t.overhead.step") as sp:
                x = f(x)
                jax.block_until_ready(x)
            prof.record_step({"device_execute": sp.seconds})
            out.append(time.perf_counter() - t0)
        return out

    # per-STEP minima: on a loaded CI share, min over 60 individual step
    # samples finds a quiet window per arm where min-of-loop-totals
    # cannot (one co-scheduled suite poisons a whole loop). Bounded
    # retries absorb pathological load; the bound itself stays 2%. (On
    # a 2 ms step the two arms' minima differ by +-5% from one attempt
    # to the next on the sandbox's host, spans or none: PR 25 found the
    # three attempts this had failing 7 times in 15 on the code before
    # it, and 2 in 15 after, so there are five.)
    overhead = None
    for _attempt in range(5):
        base, span_t = [], []
        for _ in range(5):  # interleaved: load hits both arms alike
            base.extend(plain())
            span_t.extend(spanned())
        overhead = min(span_t) / min(base)
        if overhead <= 1.02:
            break
    assert overhead <= 1.02, (
        f"span overhead {overhead:.4f}x exceeds the 2% bound "
        f"(plain min-step {min(base):.5f}s vs spanned "
        f"{min(span_t):.5f}s)")


# ------------------------------------------------- HBM + compile telemetry

def test_memory_stats_export_on_cpu_devices():
    """CPU PJRT devices return None from memory_stats(): the exporter
    reports the device with an EMPTY entry (telemetry absent, device
    present) instead of dropping or crashing."""
    import jax

    stats = hbm_stats()
    assert stats, "no devices reported"
    for label, entry in stats.items():
        assert label.startswith(jax.devices()[0].platform)
        assert entry == {}  # no HBM telemetry on CPU — and no crash


def test_memory_stats_export_with_real_stats():
    class FakeDev:
        platform = "tpu"
        id = 3

        def memory_stats(self):
            return {"bytes_in_use": 1024, "peak_bytes_in_use": 4096,
                    "bytes_limit": 16 << 30}

    class DeadDev:
        platform = "tpu"
        id = 4

        def memory_stats(self):
            raise RuntimeError("backend gone")

    out = hbm_stats(devices=[FakeDev(), DeadDev()])
    assert out["tpu:3"] == {"bytes_in_use": 1024,
                            "peak_bytes_in_use": 4096,
                            "bytes_limit": 16 << 30}
    assert out["tpu:4"] == {}
    from ray_tpu.util.metrics import get_metric

    g = get_metric("ray_tpu_hbm_bytes_in_use")
    samples = {tuple(sorted(t.items())): v for _, t, v in g._samples()}
    assert samples[(("device", "tpu:3"),)] == 1024.0
    g = get_metric("ray_tpu_hbm_bytes_peak")
    samples = {tuple(sorted(t.items())): v for _, t, v in g._samples()}
    assert samples[(("device", "tpu:3"),)] == 4096.0


def test_compile_events_on_forced_cache_miss():
    """A fresh jit program (guaranteed XLA cache miss) must emit a
    compile.start/compile.end pair into the event log and attribute its
    seconds to the step that compiled."""
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import event_log

    prof = DeviceStepProfiler("miss")
    marker = float(time.time() % 997)  # unique constant -> fresh program

    @jax.jit
    def fresh(x):
        return x * marker + jnp.float32(1.5)

    before = [e for e in list(event_log._ring)
              if e["type"].startswith("compile.")]
    with span("t.miss.step") as sp:
        jax.block_until_ready(fresh(jnp.ones((8, 8))))
    prof.record_step({"device_execute": sp.seconds})
    after = [e for e in list(event_log._ring)
             if e["type"].startswith("compile.")]
    new = after[len(before):]
    ends = [e for e in new if e["type"] == "compile.end"]
    starts = [e for e in new if e["type"] == "compile.start"]
    assert ends and starts, "forced cache miss emitted no compile events"
    assert all(e["data"]["duration_s"] > 0 for e in ends)
    assert all(e["data"]["t_start"] <= e["time"] for e in starts)
    rep = prof.report(emit_event=False)
    assert rep["compile_s"] > 0


# ------------------------------------------------- engine + span rendering

def test_engine_decode_wave_phases():
    import jax

    from ray_tpu.inference.engine import GenerationConfig
    from ray_tpu.inference.paged_engine import PagedInferenceEngine
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    eng = PagedInferenceEngine(params, cfg, max_batch=2, max_len=128)
    eng.profiler.reset()
    out = eng.generate([[1, 2, 3], [4, 5, 6]],
                       GenerationConfig(max_new_tokens=8))
    assert [len(o) for o in out] == [8, 8]
    phases = eng.stats()["device_phases"]
    assert phases["steps"] >= 1
    assert phases["device_execute_frac"] + phases["compile_frac"] > 0
    assert phases["reply_frac"] >= 0
    rep = eng.profiler.report(emit_event=False)
    # decode waves account 7 of each request's 8 tokens — the first token
    # is sampled by the admission prefill (the "prefill" phase), not a wave
    assert sum(r.get("tokens") or 0 for r in rep["recent_steps"]) == 14


def test_steps_to_spans_chrome_merge():
    from ray_tpu._private.tracing import trace_chrome

    prof = DeviceStepProfiler("spans")
    prof.record_step({"input_wait": 0.1, "device_execute": 0.5,
                      "reply": 0.05}, tokens=7)
    rep = prof.report(emit_event=False)
    spans = steps_to_spans(rep, "worker:123")
    names = {s["name"] for s in spans}
    assert "spans.step" in names
    assert "spans:device_execute" in names
    trace = trace_chrome(spans)
    lanes = {e["pid"] for e in trace if e.get("ph") == "X"}
    assert lanes == {"worker:123"}
    # phases nest back-to-back inside the step slice
    step_ev = next(e for e in trace if e["name"] == "spans.step")
    phase_ev = [e for e in trace if ":" in e["name"]]
    assert all(e["ts"] >= step_ev["ts"] for e in phase_ev)


# ------------------------------------------------- cluster e2e + CLI

def _wait_for(fn, timeout=30.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if fn():
            return True
        time.sleep(0.25)
    return False


def test_profile_device_fanout_and_cli_chrome(ray_start_regular, capsys,
                                              tmp_path):
    """The acceptance path: a live worker runs profiled device steps, a
    task produces PR 1 stage spans, and `ray-tpu profile --device
    --chrome` merges both into one chrome trace."""

    @ray_tpu.remote
    class Dev:
        def run_steps(self):
            from ray_tpu._private.device_profiler import get_profiler

            p = get_profiler("train")
            for _ in range(3):
                p.record_step({"input_wait": 0.01, "h2d": 0.002,
                               "device_execute": 0.03, "reply": 0.001},
                              tokens=16)
            return os.getpid()

    w = Dev.remote()
    pid = ray_tpu.get(w.run_steps.remote(), timeout=60)

    # raylet fan-out: no pid -> every worker on the node answers
    from ray_tpu._raylet import get_core_worker

    cw = get_core_worker()
    found = None
    for n in cw._gcs.call("get_all_node_info", {}):
        if not n.alive:
            continue
        r = cw._peers.get(n.raylet_address).call(
            "profile_worker", {"kind": "device"}, timeout=60)
        workers = r.get("workers") or {}
        if pid in workers and "train" in (
                workers[pid].get("profilers") or {}):
            found = workers[pid]["profilers"]["train"]
            break
    assert found is not None, "device fan-out never reached the worker"
    assert found["steps"] == 3
    assert found["input_wait_frac"] > 0

    # stage spans need a finished task in the GCS event stream
    from ray_tpu.util.state.api import list_tasks

    assert _wait_for(lambda: any(
        e.get("stages") for e in list_tasks(limit=100_000,
                                            raw_events=True)))

    from ray_tpu.scripts.scripts import main as cli_main

    chrome_path = str(tmp_path / "device_trace.json")
    assert cli_main(["profile", "--device", "--chrome", chrome_path]) == 0
    out = capsys.readouterr().out
    assert "train" in out and "input_wait" in out
    with open(chrome_path) as f:
        trace = json.load(f)
    lanes = {e["pid"] for e in trace if e.get("ph") == "X"}
    # ONE trace, two worlds: device-phase lanes AND task-stage lanes
    assert any(str(p).startswith("worker:") for p in lanes), lanes
    assert "tasks" in lanes, lanes
    names = {e["name"] for e in trace}
    assert "train:device_execute" in names
    assert any(":execute" in n for n in names)  # PR 1 stage span


def test_snapshot_all_includes_registry_and_compile():
    get_profiler("snap-reg").record_step({"device_execute": 0.01})
    snap = snapshot_all()
    assert "snap-reg" in snap["profilers"]
    assert "compile_s" in snap["compile"]
    assert isinstance(snap["hbm"], dict)


# ------------------------------------- jax's jit events as spans, host.gc

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"


def _jit_spans_of(feed):
    """The `jit.*` aggregate that `feed()` leaves when run on a thread of
    its own (a thread nests its jit events by time: a fresh one has none)."""
    import threading

    before = dp.snapshot()
    t = threading.Thread(target=feed)
    t.start()
    t.join()
    return {k: v for k, v in dp.delta(dp.snapshot(), before)["spans"].items()
            if k.startswith("jit.")}


def test_nested_trace_events_give_self_times_that_sum_to_the_outer():
    t = time.time()

    def feed():  # inner first, as jax fires them
        dp._on_event_time_span(_TRACE, t + 1.0, t + 2.0, fun_name="inner")
        dp._on_event_time_span(_TRACE, t + 3.5, t + 4.0, fun_name="leaf")
        dp._on_event_time_span(_TRACE, t + 3.0, t + 5.0, fun_name="inner2")
        dp._on_event_time_span(_TRACE, t, t + 10.0, fun_name="outer")
        dp._on_event_time_span(_LOWER, t + 10.0, t + 12.0, fun_name="outer")

    got = _jit_spans_of(feed)
    trace = got["jit.trace"]
    assert trace["count"] == 4
    assert trace["total_s"] == pytest.approx(1.0 + 0.5 + 2.0 + 10.0, abs=1e-3)
    assert trace["max_s"] == pytest.approx(10.0, abs=1e-3)
    # 1 + 0.5 + (2 - 0.5) + (10 - 1 - 2): the time some trace was open
    assert trace["self_s"] == pytest.approx(10.0, abs=1e-3)
    # a sibling that began after the outer trace ended is no child of it
    assert got["jit.lower"]["self_s"] == pytest.approx(2.0, abs=1e-3)


def test_a_cache_hits_compile_leaves_the_load_out_of_its_self_time():
    loads = dp.compile_stats()["cache_loads"]

    def feed():
        t0 = time.time() - 0.050
        dp._on_event_duration(_SAVED, -0.0019)       # slower than compiling
        dp._on_event_duration(_CACHE_LOAD, 0.030)    # ends now
        dp._on_event_time_span(_COMPILE, t0, time.time() + 0.001,
                               fun_name="jit(f)")

    got = _jit_spans_of(feed)
    assert set(got) == {"jit.cache_load", "jit.compile"}
    assert got["jit.cache_load"]["total_s"] == pytest.approx(0.030, abs=1e-4)
    compile_ = got["jit.compile"]
    assert compile_["total_s"] >= 0.050
    assert compile_["self_s"] == pytest.approx(
        compile_["total_s"] - 0.030, abs=1e-4)
    assert dp.compile_stats()["cache_loads"] == loads + 1


@pytest.mark.parametrize("call", [
    lambda t: dp._on_event_time_span(_TRACE, t, t - 1.0),          # negative
    lambda t: dp._on_event_time_span(_TRACE, t, float("nan")),
    lambda t: dp._on_event_time_span(_TRACE, float("nan"), t),
    lambda t: dp._on_event_time_span(_TRACE, t, float("inf")),
    lambda t: dp._on_event_time_span(_TRACE, "then", None),
    lambda t: dp._on_event_time_span("/jax/some/other_duration", t, t + 1),
    lambda t: dp._on_event_time_span(_SAVED, t, t + 1.0),
    lambda t: dp._on_event_duration(_CACHE_LOAD, -0.0019),
    lambda t: dp._on_event_duration(_CACHE_LOAD, float("nan")),
    lambda t: dp._on_event_duration(_CACHE_LOAD, "soon"),
    lambda t: dp._on_event_duration(_SAVED, 3.0),
    lambda t: dp._on_event_duration("/jax/some/other_sec", 1.0, extra=1),
], ids=["negative", "nan_end", "nan_start", "inf", "no_numbers",
        "unknown_event", "saved_as_span", "negative_load", "nan_load",
        "load_no_number", "saved", "unknown_duration"])
def test_jit_listeners_record_nothing_and_raise_nothing(call):
    assert _jit_spans_of(lambda: call(time.time())) == {}


def test_jit_listeners_take_any_keyword_and_stay_out_of_the_ring():
    t = time.time()
    got = _jit_spans_of(lambda: dp._on_event_time_span(
        _LOWER, t, t + 0.25, fun_name="f", something_new=7))
    assert got["jit.lower"]["count"] == 1
    # thousands of nested traces a model would evict every other record
    assert all(not r["name"].startswith("jit.")
               for r in dp.snapshot(recent=dp.RING_RECORDS)["recent"])


def test_a_jax_without_time_spans_is_heard_through_its_durations(
        monkeypatch):
    """Where `jax.monitoring` hands over durations only, every stage is a
    record that ends when it is reported; where it hands over both ends,
    a duration is recorded only for the event that has no time span."""
    def feed():
        dp._on_event_duration(_TRACE, 0.5, fun_name="f")
        dp._on_event_duration(_LOWER, 0.25)
        dp._on_event_duration(_CACHE_LOAD, 0.125)

    monkeypatch.setattr(dp, "_time_span_listener", True)
    assert set(_jit_spans_of(feed)) == {"jit.cache_load"}
    monkeypatch.setattr(dp, "_time_span_listener", False)
    got = _jit_spans_of(feed)
    assert {k: round(v["total_s"], 4) for k, v in got.items()} == {
        "jit.trace": 0.5, "jit.lower": 0.25, "jit.cache_load": 0.125}


def test_a_jit_inside_a_span_is_left_out_of_its_self_time():
    def feed():
        with span("test.around_jit"):
            t = time.time()
            time.sleep(0.02)
            dp._on_event_time_span(_TRACE, t, time.time(), fun_name="f")

    before = dp.snapshot()
    _jit_spans_of(feed)
    got = dp.delta(dp.snapshot(), before)["spans"]
    around, trace = got["test.around_jit"], got["jit.trace"]
    assert trace["total_s"] >= 0.02
    assert around["self_s"] == pytest.approx(
        around["total_s"] - trace["total_s"], abs=2e-3)


def test_host_gc_records_full_collections_only():
    import gc

    dp.install_compile_listener()
    assert dp._on_gc in gc.callbacks
    was = gc.isenabled()
    gc.disable()   # no collection but the two asked for
    try:
        count = lambda: dp.snapshot()["spans"].get(  # noqa: E731
            "host.gc", {"count": 0})["count"]
        n = count()
        gc.collect(0)
        gc.collect(1)
        assert count() == n
        gc.collect(2)
        assert count() == n + 1
    finally:
        if was:
            gc.enable()
    assert dp.snapshot()["spans"]["host.gc"]["max_s"] > 0
    assert all(r["name"] != "host.gc"   # the aggregate only, as `jit.*`
               for r in dp.snapshot(recent=dp.RING_RECORDS)["recent"])
    # a callback that is handed nonsense records nothing, raises nothing
    dp._on_gc("stop", {})
    dp._on_gc("start", None)
    assert count() == n + 1


@pytest.mark.parametrize("other", [
    None, {}, {"spans": None, "counters": None}, [], "spans", 7,
    {"spans": {"x": {"count": 1}}},                  # fields missing
    {"spans": {"x": {"count": 1, "total_s": "a", "max_s": 0, "self_s": 0}}},
    {"spans": {"x": 3}, "counters": {"n": 1}},
    {"spans": {}, "counters": {"n": "many"}},
], ids=["none", "empty", "nones", "list", "str", "int", "fields_missing",
        "not_numbers", "not_an_aggregate", "counter_no_number"])
def test_merge_of_what_is_no_snapshot_is_a_no_op(other):
    before = dp.snapshot()
    with span("test.merge_noop"):
        dp.merge(other)
    got = dp.delta(dp.snapshot(), before)
    assert "x" not in got["spans"] and "n" not in got["counters"]
    noop = got["spans"]["test.merge_noop"]   # nothing grafted under it
    assert noop["self_s"] == pytest.approx(noop["total_s"], abs=1e-9)


def test_a_snapshot_names_what_the_listeners_feed_from_installation_on():
    """`count` 0 says "listened, saw none" (a process whose every program
    missed the cache loaded for 0 s; one that never collected paused for
    0 s); a process that never installed the listeners lacks the names."""
    import subprocess
    import sys

    dp.install_compile_listener()
    spans = dp.snapshot()["spans"]
    for name in ("jit.trace", "jit.lower", "jit.compile", "jit.cache_load",
                 "host.gc"):
        assert spans[name]["count"] >= 0 and spans[name]["max_s"] >= 0.0
    code = ("from ray_tpu._private import device_profiler as dp\n"
            "with dp.span('x'): pass\n"
            "assert set(dp.snapshot()['spans']) == {'x'}")
    subprocess.run([sys.executable, "-c", code], check=True)
