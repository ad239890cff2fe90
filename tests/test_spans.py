"""The span layer (`_private/device_profiler.span`): the aggregate's
arithmetic, the xplane under a running `jax.profiler` trace, and the
places the spans go (trainer start-up, the engine's service loop, the
replica's hot path)."""

import glob
import os
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu._private import device_profiler as dp
from ray_tpu._private.device_profiler import count, record, span

pytestmark = pytest.mark.profiling


# ------------------------------------------------------------ the aggregate

def test_aggregate_count_total_self_and_parent_under_nesting():
    before = dp.snapshot()
    for _ in range(2):
        with span("t.outer", k=1) as outer:
            with span("t.inner"):
                time.sleep(0.01)
            with span("t.inner") as inner:
                time.sleep(0.01)
            time.sleep(0.005)
    got = dp.delta(dp.snapshot(), before)["spans"]
    assert got["t.outer"]["count"] == 2 and got["t.inner"]["count"] == 4
    # a parent's self time is its duration less what its children cover
    assert got["t.outer"]["self_s"] == pytest.approx(
        got["t.outer"]["total_s"] - got["t.inner"]["total_s"], abs=1e-9)
    assert 0.008 <= got["t.outer"]["self_s"] <= got["t.outer"]["total_s"]
    assert got["t.inner"]["self_s"] == got["t.inner"]["total_s"] >= 0.04
    assert got["t.outer"]["max_s"] >= got["t.outer"]["total_s"] / 2
    assert outer.seconds > inner.seconds >= 0.01
    # (a background thread of an earlier test's cluster may record between)
    recent = [r for r in dp.snapshot(recent=64)["recent"]
              if r["name"].startswith("t.")][-3:]
    assert [(r["name"], r["parent"]) for r in recent] == [
        ("t.inner", "t.outer"), ("t.inner", "t.outer"), ("t.outer", None)]
    assert recent[-1]["attrs"] == {"k": 1}
    assert recent[0]["start"] >= recent[-1]["start"]
    assert recent[0]["end"] <= recent[-1]["end"]


def test_aggregate_across_threads_and_after_they_end():
    before = dp.snapshot()
    seen = []

    def work():
        with span("t.threaded"):
            with span("t.threaded.child"):
                time.sleep(0.002)
        count("t.events", 2)
        seen.append(threading.current_thread().name)

    with span("t.main"):
        threads = [threading.Thread(target=work, name=f"t-span-{i}")
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    got = dp.delta(dp.snapshot(), before)
    assert got["spans"]["t.threaded"]["count"] == 4
    assert got["counters"]["t.events"] == 8
    # another thread's span is no child of this thread's open span
    assert got["spans"]["t.main"]["self_s"] == got["spans"]["t.main"]["total_s"]
    by_thread = {r["thread"]: r["parent"]
                 for r in dp.snapshot(recent=64)["recent"]
                 if r["name"] == "t.threaded"}
    assert set(seen) <= set(by_thread) and set(by_thread.values()) == {None}
    # a thread that starts later retires the ended ones' tables: the
    # totals stay
    t = threading.Thread(target=work, name="t-span-late")
    t.start()
    t.join()
    assert dp.delta(dp.snapshot(), before)["spans"]["t.threaded"][
        "count"] == 5


def test_record_counts_a_region_timed_elsewhere():
    before = dp.snapshot()
    t0 = dp.now()
    record("t.request", t0, t0 + 3_000_000, req_id=7, tokens=5)
    record("t.request", t0, t0 + 1_000_000, req_id=8, tokens=1)
    got = dp.delta(dp.snapshot(), before)["spans"]["t.request"]
    assert got["count"] == 2
    assert got["total_s"] == pytest.approx(0.004)
    assert got["max_s"] == pytest.approx(0.003)
    last = dp.snapshot(recent=1)["recent"][0]
    assert last["attrs"] == {"req_id": 8, "tokens": 1}
    assert last["end"] - last["start"] == pytest.approx(0.001, abs=1e-6)


def test_merge_grafts_another_process_under_the_open_span():
    """What rank 0 of a gang hands back: merged under the same names, and
    left out of the self time of the round the driver waited in."""
    worker = {"pid": 1, "counters": {"t.w.events": 3}, "spans": {
        "t.w.open": {"count": 1, "total_s": 0.03, "max_s": 0.03,
                     "self_s": 0.03},
        "t.w.build": {"count": 1, "total_s": 0.05, "max_s": 0.05,
                      "self_s": 0.02}}}   # 0.03 of it was t.w.open
    before = dp.snapshot()
    with span("t.round"):
        time.sleep(0.06)
        dp.merge(worker)
    got = dp.delta(dp.snapshot(), before)
    assert got["spans"]["t.w.open"]["total_s"] == pytest.approx(0.03)
    assert got["spans"]["t.w.build"]["self_s"] == pytest.approx(0.02)
    assert got["counters"]["t.w.events"] == 3
    rnd = got["spans"]["t.round"]
    assert rnd["total_s"] >= 0.06
    assert rnd["self_s"] == pytest.approx(rnd["total_s"] - 0.05, abs=1e-6)


def test_delta_of_two_snapshots():
    with span("t.delta"):
        pass
    before = dp.snapshot()
    with span("t.delta"):
        time.sleep(0.002)
    count("t.delta.n")
    got = dp.delta(dp.snapshot(), before)
    assert got["spans"]["t.delta"]["count"] == 1
    assert got["spans"]["t.delta"]["total_s"] >= 0.002
    assert got["counters"] == {"t.delta.n": 1}
    assert dp.delta(dp.snapshot(), dp.snapshot())["spans"] == {}
    # a name first counted in between rides along at 0; one that stood
    # before and did not move does not
    before = dp.snapshot()
    count("t.delta.none_of_them", 0)
    count("t.delta.n", 0)
    assert dp.delta(dp.snapshot(), before)["counters"] == {
        "t.delta.none_of_them": 0}


def test_span_survives_an_exception_and_leaves_the_stack_clean():
    before = dp.snapshot()
    with pytest.raises(ValueError):
        with span("t.raises"):
            with span("t.raises.inner"):
                raise ValueError("x")
    with span("t.after"):
        pass
    got = dp.delta(dp.snapshot(), before)["spans"]
    assert got["t.raises"]["count"] == got["t.raises.inner"]["count"] == 1
    assert dp.snapshot(recent=1)["recent"][0]["parent"] is None


def test_span_in_a_fresh_interpreter_leaves_jax_out():
    code = (
        "import sys\n"
        "from ray_tpu._private.device_profiler import span, count, snapshot\n"
        "with span('a', n=1):\n"
        "    count('c')\n"
        "s = snapshot(recent=1)\n"
        "assert s['spans']['a']['count'] == 1 and s['counters'] == {'c': 1}\n"
        "assert 'jax' not in sys.modules, 'span imported jax'\n"
        "import ray_tpu.train, ray_tpu.serve.llm\n"
        "assert 'jax' not in sys.modules, 'a driver-side import took jax'\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=root)
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_span_cost_with_no_trace_running():
    """Enter + exit: microseconds, with jax in the process and no trace
    (the annotation is then a no-op of the profiler's)."""
    import jax  # noqa: F401 — the annotation path is the one measured

    n = 20_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            with span("t.cost"):
                pass
        best = min(best, (time.perf_counter_ns() - t0) / n)
    assert best < 20_000, f"{best:.0f} ns a span"


# ------------------------------------------------- on the profiler's clock

def test_xplane_holds_rt_events_nested_on_the_calling_thread(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("x.outer", rows=3):
            with span("x.inner"):
                f(x).block_until_ready()

        def other():
            with span("x.other"):
                time.sleep(0.001)

        t = threading.Thread(target=other)
        t.start()
        t.join()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    (host,) = [p for p in ProfileData.from_file(path).planes
               if p.name == "/host:CPU"]
    lines = {}
    for i, line in enumerate(host.lines):
        for e in line.events:
            if e.name.startswith("rt.x."):
                lines.setdefault(i, {})[e.name] = (
                    e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
    by_name = {name: i for i, evs in lines.items() for name in evs}
    assert set(by_name) == {"rt.x.outer", "rt.x.inner", "rt.x.other"}
    # the calling thread's line holds both, nested as entered; the other
    # thread's span lies on a line of its own
    assert by_name["rt.x.outer"] == by_name["rt.x.inner"]
    assert by_name["rt.x.other"] != by_name["rt.x.outer"]
    o0, o1, stats = lines[by_name["rt.x.outer"]]["rt.x.outer"]
    i0, i1, _ = lines[by_name["rt.x.inner"]]["rt.x.inner"]
    assert o0 <= i0 and i1 <= o1
    assert stats.get("rows") == 3


# ------------------------------------------------------- where the spans go

def test_fit_leaves_gang_spans_that_cover_the_trainer_start(
        ray_start_regular, tmp_path):
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.train.backend import JaxConfig

    def _train_fn(config):  # a closure: shipped by value
        t_enter = time.time()
        from ray_tpu import train

        train.report({"t_enter": t_enter,
                      "mesh": dict(train.get_mesh().shape)})

    before = dp.snapshot()
    t_fit = time.time()
    result = JaxTrainer(
        _train_fn, train_loop_config={},
        jax_config=JaxConfig(
            platform="cpu", mesh_config=MeshConfig(fsdp=2), env_vars={
                "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}),
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name="spans", storage_path=str(tmp_path)),
    ).fit()
    assert result.error is None, result.error
    trainer_start_s = result.metrics["t_enter"] - t_fit
    got = dp.delta(dp.snapshot(), before)["spans"]
    gang = {k: v for k, v in got.items() if k.startswith("train.gang.")}
    assert set(gang) == {
        "train.gang.place", "train.gang.backend_init", "train.gang.mesh",
        "train.gang.platform_check", "train.gang.session",
        "train.gang.launch"}
    assert all(v["count"] == 1 for v in gang.values())
    covered = sum(v["total_s"] for v in gang.values())
    assert covered >= 0.9 * trainer_start_s, (covered, trainer_start_s)
    assert got["train.fit"]["total_s"] >= covered
    # rank 0's spans rode back on the rounds' return values
    assert got["train.worker.open_chip"]["count"] >= 1
    assert got["train.worker.mesh_build"]["count"] == 1
    # ... and the mesh round's self time leaves them out
    mesh = gang["train.gang.mesh"]
    assert mesh["self_s"] <= mesh["total_s"] - got[
        "train.worker.mesh_build"]["total_s"] + 1e-6


def _fit_on_cpu(train_fn, tmp_path, name):
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.train.backend import JaxConfig

    return JaxTrainer(
        train_fn, train_loop_config={},
        jax_config=JaxConfig(platform="cpu", mesh_config=MeshConfig(fsdp=1)),
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(name=name, storage_path=str(tmp_path)),
    ).fit()


def test_fit_brings_the_gang_workers_record_home(
        ray_start_regular, tmp_path, monkeypatch):
    """(These would stand in tests/test_train.py, which is marked slow as a
    whole: tier-1 would never run them.) What the worker timed and counted
    after its session began is in the DRIVER's aggregate once `fit()`
    returns; what it had handed back before is not counted again."""
    from ray_tpu.train._internal.backend_executor import BackendExecutor

    def _train_fn(config):  # a closure: shipped by value
        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu import train
        from ray_tpu._private.device_profiler import count

        opt = optax.sgd(0.1)
        state, shardings = train.init_train_state(
            lambda key: {"w": jnp.zeros((4,))}, opt, {"w": (None,)},
            train.get_mesh(), jax.random.PRNGKey(0))
        step = train.make_train_step(
            lambda params, batch: jnp.sum((params["w"] - batch) ** 2),
            opt, shardings)
        for _ in range(2):
            state, m = step(state, jnp.ones((4,)))
        count("test.bumped_in_train_fn", 3)
        train.report({"loss": float(m["loss"])})

    at_finish = {}
    finish = BackendExecutor.finish

    def _finish(self):
        at_finish.update(dp.snapshot())
        finish(self)

    monkeypatch.setattr(BackendExecutor, "finish", _finish)
    before = dp.snapshot()
    result = _fit_on_cpu(_train_fn, tmp_path, "home")
    assert result.error is None, result.error
    assert result.metrics == {"loss": pytest.approx(2.56)}
    after = dp.snapshot()
    got = dp.delta(after, before)
    spans = got["spans"]
    assert spans["train.step.dispatch"]["count"] == 2
    assert spans["train.init_state"]["count"] == 1
    assert spans["train.report"]["count"] == 1
    # the first call traces, lowers and compiles the step: jax said so
    for name in ("jit.trace", "jit.lower", "jit.compile"):
        assert spans[name]["count"] >= 2, name   # the init and the step
        assert 0 < spans[name]["self_s"] <= spans[name]["total_s"] + 1e-9
    # (`max_s` is the later snapshot's: a longer step of another test of this
    # process would stand there; this fit's two dispatches' sum bounds it)
    first = min(spans["train.step.dispatch"]["max_s"],
                spans["train.step.dispatch"]["total_s"])
    # ... and the spans open around those jits leave them out of their own
    # time: what they do not count as theirs is jit self time (an identity
    # of the accounting, whatever the host's load does to the first call)
    inside = sum(spans[n]["total_s"] - spans[n]["self_s"]
                 for n in ("train.step.dispatch", "train.init_state"))
    assert 0 < inside <= sum(spans[n]["self_s"] for n in spans
                             if n.startswith("jit.")) + 1e-6
    assert got["counters"]["test.bumped_in_train_fn"] == 3
    # none of this was in the driver before `finish()` ...
    assert "train.step.dispatch" not in dp.delta(at_finish, before)["spans"]
    # ... and the start-up spans, handed back on their rounds, not twice
    for name in ("train.worker.open_chip", "train.worker.mesh_build",
                 "train.worker.chip_wait"):
        assert after["spans"][name]["count"] == \
            at_finish["spans"][name]["count"], name
    # the wait for a predecessor's chips is a span of its own, one a call
    # that opens the backend, beside `open_chip` and not inside it
    started = dp.delta(at_finish, before)["spans"]
    assert started["train.worker.chip_wait"]["count"] == \
        started["train.worker.open_chip"]["count"] == 2
    assert started["train.gang.mesh"]["self_s"] <= \
        started["train.gang.mesh"]["total_s"] - \
        started["train.worker.chip_wait"]["total_s"] + 1e-6
    # `train.fit`'s self time is the driver's own
    fit = spans["train.fit"]
    assert fit["self_s"] <= fit["total_s"] - first + 1e-6
    # nothing but names and numbers came over, no ring
    assert all(r["name"] != "train.step.dispatch"
               for r in dp.snapshot(recent=dp.RING_RECORDS)["recent"])


@pytest.mark.parametrize("how", ["raises", "a_string", "wrong_shapes"])
def test_fit_is_the_same_when_finish_brings_nothing_home(
        ray_start_regular, tmp_path, monkeypatch, how):
    from ray_tpu.train._internal import worker_group

    class _Worker(worker_group.TrainWorker):   # shipped by value
        def finish(self, timeout: float = 30.0):
            super().finish(timeout)
            if how == "raises":
                raise RuntimeError("no record")
            return "garbage" if how == "a_string" else {
                "spans": {"train.step.dispatch": 3}, "counters": [1]}

    monkeypatch.setattr(worker_group, "TrainWorker", _Worker)

    def _train_fn(config):
        from ray_tpu import train

        train.report({"step": 1})
        train.report({"step": 2})

    before = dp.snapshot()
    result = _fit_on_cpu(_train_fn, tmp_path, how)
    assert result.error is None, result.error
    assert result.metrics == {"step": 2}
    assert result.checkpoint is None
    assert result.path and os.path.isdir(result.path)
    got = dp.delta(dp.snapshot(), before)
    assert got["spans"]["train.fit"]["count"] == 1
    assert "train.step.dispatch" not in got["spans"]
    assert "train.report" not in got["spans"]


@pytest.fixture(scope="module")
def tiny_engine():
    import jax

    from ray_tpu.inference.paged_engine import PagedInferenceEngine
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return lambda: PagedInferenceEngine(
        params, cfg, max_batch=2, max_len=128, decode_chunk=4)


def test_serve_stream_request_records_occupancy_and_phases(tiny_engine):
    from ray_tpu.inference.engine import GenerationConfig

    eng = tiny_engine()

    def run(names):
        t_sent = dp.now()
        batch = [(name, [1 + i, 2, 3], 6, t_sent)
                 for i, name in enumerate(names)]

        def feed(_block):
            out, batch[:] = list(batch), []
            return out, (), True

        tokens = {}
        for req, tok, _done in eng.serve_stream(feed, GenerationConfig()):
            tokens.setdefault(req, []).append(tok)
        return {k: len(v) for k, v in tokens.items()}

    run(["w0", "w1", "w2"])   # compiles every shape the second run uses
    eng.profiler.reset()
    before = eng.stats()
    t_mark = (dp.now() + dp._EPOCH_NS) * 1e-9   # the ring's clock
    assert run(["r0", "r1", "r2"]) == {"r0": 6, "r1": 6, "r2": 6}
    after = eng.stats()
    spans = dp.delta({"pid": 0, "spans": after["spans"],
                      "counters": after["counters"]},
                     {"pid": 0, "spans": before["spans"],
                      "counters": before["counters"]})
    got, counters = spans["spans"], spans["counters"]
    # one record a request, and one queue wait each (max_batch 2: the
    # third waited for a slot)
    assert got["engine.request"]["count"] == 3
    assert got["engine.queue_wait"]["count"] == 3
    assert got["engine.admit_wave"]["count"] == 2
    assert 1 <= got["engine.admit"]["count"] <= 2
    assert got["engine.decode_chunk"]["count"] >= 2
    assert got["engine.fanout"]["count"] >= got["engine.decode_chunk"]["count"]
    assert got["engine.feed"]["count"] >= 1
    # every token but each request's first (the prefill samples that one)
    # is one active row of one decode step
    assert counters["decode.row_steps_active"] == 3 * 6 - 3
    assert counters["decode.row_steps_capacity"] >= counters[
        "decode.row_steps_active"]
    assert counters["decode.row_steps_capacity"] % eng.max_batch == 0
    # a row of length L attends to L tokens: prompts of 3, five steps each
    assert counters["decode.kv_tokens_attended"] == 3 * sum(range(4, 9))
    ring = [r for r in dp.snapshot(recent=256)["recent"]
            if r["start"] >= t_mark]
    # the wait is part of the request's one record, not a second one
    assert not [r for r in ring if r["name"] == "engine.queue_wait"]
    records = {r["attrs"]["req_id"]: r for r in ring
               if r["name"] == "engine.request"}
    assert set(records) >= {"r0", "r1", "r2"}
    for rec in (records[k] for k in ("r0", "r1", "r2")):
        a = rec["attrs"]
        assert a["tokens"] == 6 and a["outcome"] == "ok"
        assert a["preemptions"] == 0
        assert 0 <= a["admitted_s"] <= a["first_token_s"] <= (
            rec["end"] - rec["start"]) + 1e-6
    # the third request's wait is the first two's whole decode
    assert records["r2"]["attrs"]["admitted_s"] > records["r0"]["attrs"][
        "first_token_s"]
    # the phases the benchmark reads keep their keys, summed off the spans
    phases = after["device_phases"]["phase_seconds"]
    assert {"input_wait", "prefill", "device_execute", "reply"} <= set(phases)
    # prefill is all of admission, the first tokens' hand-off with it;
    # reply is the decoded tokens' fan-out alone
    assert phases["prefill"] == pytest.approx(
        got["engine.admit"]["total_s"], abs=1e-4)
    assert got["engine.admit"]["total_s"] >= got[
        "engine.admit_wave"]["total_s"]
    assert phases["device_execute"] == pytest.approx(
        got["engine.decode_chunk"]["total_s"], abs=1e-4)
    assert phases["reply"] == pytest.approx(
        sum(r["end"] - r["start"] for r in ring
            if r["name"] == "engine.fanout" and not r["attrs"]["first"]),
        abs=1e-4)
    first = [r for r in ring
             if r["name"] == "engine.fanout" and r["attrs"]["first"]]
    assert first and all(r["parent"] == "engine.admit" for r in first)


def test_replica_hot_path_makes_no_registry_lookup(tiny_engine, monkeypatch):
    """Handles are taken at construction; the token counter moves once a
    chunk, by the tokens delivered, and its total is what it was."""
    from ray_tpu.serve.llm import metrics as llm_metrics
    from ray_tpu.serve.llm.engine import LLMEngineReplica
    from ray_tpu.util import metrics as um

    replica = LLMEngineReplica(tiny_engine, {"max_new_tokens": 12})
    try:
        assert replica.generate([1, 2, 3], max_new_tokens=2)  # warm
        lookups = []
        real = um.get_metric
        monkeypatch.setattr(
            um, "get_metric", lambda name: lookups.append(name) or real(name))
        tokens = um.get_metric(llm_metrics.TOKENS_NAME)
        incs = []
        real_inc = tokens.inc
        monkeypatch.setattr(
            tokens, "inc",
            lambda value=1.0, tags=None: incs.append(value) or real_inc(
                value, tags=tags))
        lookups.clear()
        total_before = sum(v for _, _, v in tokens._samples())
        out = replica.generate([4, 5, 6], max_new_tokens=12)
        assert len(out) == 12
        assert lookups == [], f"registry lookups on the hot path: {lookups}"
        assert sum(v for _, _, v in tokens._samples()) - total_before == 12
        # 1 token from the prefill, then chunks of up to 4: not 12 bumps
        assert sum(incs) == 12 and len(incs) <= 5, incs
        stats = replica.get_stats()["engine"]
        assert stats["spans"]["engine.request"]["count"] >= 2
        assert "decode.row_steps_active" in stats["counters"]
    finally:
        replica.shutdown()


# ------------------------------------------- the kernels' and layers' counters

def test_a_router_ahead_of_attention_and_the_flash_kernels_are_counted():
    """The names a lowering leaves in the aggregate for a layer whose router
    reads the attention's input (`models/window_moe.py`): `moe.routed_ahead`
    beside `moe.experts_held`, `moe.rows_capacity`, `moe.gmm_calls`,
    `pattern.layers_unrolled` and `flash.window_calls`; the scope
    `moe.route` entered BEFORE the window call's `swa.attend`, the experts'
    `moe.experts` after it; and a Pallas flash call's `flash.kernels`, with
    `flash.kernels_vmem_stated` there and unmoved for a call the default
    VMEM holds (`benchmarks/metrics/flash_vmem_stated_share.json` reads the
    two)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import window_moe
    from ray_tpu.ops.flash_attention import flash_attention

    cfg = window_moe.WindowMoeConfig.tiny_ahead(
        vocab_size=64, remat=False, layers=(1,), n_experts_held=4)
    params = jax.eval_shape(lambda: window_moe.init(cfg, jax.random.PRNGKey(0)))
    before = dp.snapshot()["counters"]
    traced = jax.make_jaxpr(
        lambda p, t: window_moe.forward_hidden(p, t, cfg)[0])(
            params, jax.ShapeDtypeStruct((1, 32), jnp.int32))
    q, k = (jax.ShapeDtypeStruct((1, 256, h, 16), jnp.float32) for h in (7, 1))
    jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, use_pallas=True, block_q=128, block_k=128))(q, k, k)
    after = dp.snapshot()["counters"]
    grew = {name: after[name] - before.get(name, 0) for name in (
        "moe.routed_ahead", "moe.experts_held", "moe.rows_capacity",
        "moe.gmm_calls", "pattern.layers_unrolled", "flash.window_calls",
        "flash.kernels", "flash.kernels_vmem_stated")}
    assert grew == {
        "moe.routed_ahead": 1, "moe.experts_held": 4,
        "moe.rows_capacity": 32 * 4, "moe.gmm_calls": 3,
        "pattern.layers_unrolled": 1, "flash.window_calls": 1,
        "flash.kernels": 1, "flash.kernels_vmem_stated": 0}

    def scopes(jaxpr):
        for eqn in jaxpr.eqns:
            yield str(eqn.source_info.name_stack)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scopes(sub)

    entered = list(scopes(traced.jaxpr))
    first = lambda name: next(  # noqa: E731
        i for i, s in enumerate(entered) if name in s)
    assert first("moe.route") < first("swa.attend") < first("moe.experts")


def test_the_flash_backwards_row_statistics_are_counted_by_their_form():
    """A traced `value_and_grad` of a Pallas flash call counts, once per
    lowering of its backward pass, the HBM bytes of lse and delta as its two
    kernels take them: `flash.bwd_stat_row_bytes`, lane-dense, 4 bytes a
    number (two statistics, each in dq's one block of runs of lanes and as
    dk/dv's rows: 4 x b x h x s float32 over three operands), and
    `flash.bwd_stat_column_bytes`, what reaches a kernel as `[.., 1]`, its
    last dim padded to 128 lanes: there, and 0 (2 x b x h x s x 512 a call
    before PR 63). A trace of the forward alone counts neither."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import flash_attention

    b, s, h = 2, 384, 4
    q, k = (jax.ShapeDtypeStruct((b, s, n, 16), jnp.float32) for n in (h, 2))

    def call(q, k, v):
        return flash_attention(q, k, v, use_pallas=True, block_q=128,
                               block_k=128).sum()

    def grew(fn):
        before = dp.snapshot()["counters"]
        jax.make_jaxpr(fn)(q, k, k)
        after = dp.snapshot()["counters"]
        return {name: after.get(name, 0) - before.get(name, 0) for name in (
            "flash.bwd_stat_row_bytes", "flash.bwd_stat_column_bytes")}

    assert grew(call) == {"flash.bwd_stat_row_bytes": 0,
                          "flash.bwd_stat_column_bytes": 0}
    assert grew(jax.value_and_grad(call, argnums=(0, 1, 2))) == {
        "flash.bwd_stat_row_bytes": 2 * 2 * b * h * s * 4,
        "flash.bwd_stat_column_bytes": 0}
    assert "flash.bwd_stat_column_bytes" in dp.snapshot()["counters"]


def test_the_heads_chunks_are_counted_where_their_gradient_is_formed():
    """`blocks.chunked_ce` counts every chunk it traces, the remainder's
    too, under `ce.chunks`, and under `ce.chunks_fused` those of the forward
    rule of its `custom_vjp`, whose gradient is formed with their logits: a
    trace of the loss alone leaves the second where it was. The two names
    are what `benchmarks/metrics/ce_fused_chunk_share.json` divides."""
    import json

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import blocks

    hidden = jax.ShapeDtypeStruct((2, 29, 16), jnp.float32)
    lm_head = jax.ShapeDtypeStruct((16, 50), jnp.float32)
    targets = jnp.zeros((2, 29), jnp.int32)

    def loss(h, w):
        return blocks.chunked_ce(h, w, targets, chunk=8)

    def grew(fn):
        before = dp.snapshot()["counters"]
        jax.make_jaxpr(fn)(hidden, lm_head)
        after = dp.snapshot()["counters"]
        return {name: after[name] - before.get(name, 0)
                for name in ("ce.chunks", "ce.chunks_fused")}

    assert grew(loss) == {"ce.chunks": 4, "ce.chunks_fused": 0}
    assert grew(jax.grad(loss, argnums=(0, 1))) == {
        "ce.chunks": 4, "ce.chunks_fused": 4}
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "metrics",
            "ce_fused_chunk_share.json")) as f:
        spec = json.load(f)
    assert (spec["over"], spec["under"]) == (["ce.chunks_fused"], ["ce.chunks"])


def test_the_embeddings_gradient_rows_are_counted_by_the_form_that_sums_them(
        monkeypatch):
    """`blocks.embed_rows` counts, once per trace of its backward rule, the
    T rows of the cotangent under `embed.grad_rows` and, where the sorted
    sum forms d table, under `embed.grad_rows_sorted` too (0 is added where
    the scatter-add stands, so both names are there once either is); a
    trace of the lookup alone counts nothing. The two names are what
    `benchmarks/metrics/embed_grad_sorted_row_share.json` divides:
    `counter_readers.ratio` reads 100 where every row is summed sorted, 0
    where none is, and leaves the metric out of a program with neither."""
    import json

    import jax
    import jax.numpy as jnp

    from benchmarks import counter_readers
    from ray_tpu.models import blocks
    from ray_tpu.ops import row_sums

    names = ("embed.grad_rows", "embed.grad_rows_sorted")
    table = jax.ShapeDtypeStruct((300, 640), jnp.bfloat16)  # 5 x 128 wide
    tokens = jnp.zeros((2, 24), jnp.int32)

    def lookup(tb):
        return blocks.embed_rows(tb, tokens).astype(jnp.float32).sum()

    def grew(fn):
        before = dp.snapshot()["counters"]
        jax.make_jaxpr(fn)(table)
        after = dp.snapshot()["counters"]
        return {name: after.get(name, 0) - before.get(name, 0)
                for name in names}

    assert grew(lookup) == dict.fromkeys(names, 0)
    assert grew(jax.grad(lookup)) == {names[0]: 48, names[1]: 0}  # no TPU
    monkeypatch.setattr(row_sums, "sums_in_order",
                        lambda dtype: dtype == jnp.bfloat16)
    assert grew(jax.grad(lookup)) == {names[0]: 48, names[1]: 48}

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "metrics",
            "embed_grad_sorted_row_share.json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "counter_readers.ratio"
    assert (spec["over"], spec["under"]) == ([names[1]], [names[0]])
    read = lambda counters: counter_readers.ratio(  # noqa: E731
        spec, {"counters": counters}, {})
    assert read({names[0]: 48, names[1]: 48, "ce.chunks": 4}) == 100
    assert read({names[0]: 48, names[1]: 0}) == 0
    assert read({"ce.chunks": 4}) is None


def test_the_residual_paths_connections_are_counted_and_scoped():
    """`models/streams.py` under `models/mla_moe.py` (`hc_mult` 4): a
    lowering leaves `hc.connections` (2 a layer body traced: the scanned
    expert layers lower ONE body), `hc.sinkhorn_iters` (20 a connection),
    `hc.rows_mixed` (tokens x 4 a connection) and `hc.rows_fused` (the rows
    of the connections that took `ops/stream_mix.py`'s Pallas calls: none
    on the CPU, all of them under the tests' interpreter seam, where the
    scopes `hc.pre` and `hc.post` name the calls) in the aggregate, and the
    scopes `hc.expand`, `hc.maps`, `hc.pre`, `hc.post`, `hc.reduce` in the
    jaxpr, the maps' before the pre-mix's before the sublayer's before the
    post-mix's; a model on one stream leaves none of them. The names are
    what PERF.md section 3 and `benchmarks/metrics/hc_time_share.json` go
    by."""
    import json

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mla_moe

    names = ("hc.connections", "hc.sinkhorn_iters", "hc.rows_mixed",
             "hc.rows_fused")

    def lowered(cfg):
        params = jax.eval_shape(lambda: mla_moe.init(cfg, jax.random.PRNGKey(0)))
        before = dp.snapshot()["counters"]
        traced = jax.make_jaxpr(
            lambda p, t: mla_moe.forward_hidden(p, t, cfg)[0])(
                params, jax.ShapeDtypeStruct((2, 32), jnp.int32))
        after = dp.snapshot()["counters"]
        return traced, {n: after.get(n, 0) - before.get(n, 0) for n in names}

    def scopes(jaxpr):
        for eqn in jaxpr.eqns:
            yield str(eqn.source_info.name_stack)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scopes(sub)

    tiny = dict(vocab_size=64, remat=False, n_experts_held=4, mtp_depth=0)
    traced, grew = lowered(mla_moe.MlaMoeConfig.tiny(
        hc_mult=4, n_layers=4, **tiny))
    # a dense layer's body and the three expert layers' one
    assert grew == {"hc.connections": 2 * 2, "hc.sinkhorn_iters": 20 * 4,
                    "hc.rows_mixed": 4 * 4 * 64, "hc.rows_fused": 0}
    entered = list(scopes(traced.jaxpr))
    first = lambda name: next(  # noqa: E731
        i for i, s in enumerate(entered) if name in s)
    assert first("hc.expand") < first("hc.maps") < first("hc.pre") \
        < first("mla.attend") < first("hc.post") < first("hc.reduce")
    traced, grew = lowered(mla_moe.MlaMoeConfig.tiny(n_layers=4, **tiny))
    assert grew == dict.fromkeys(names, 0)
    assert not [s for s in scopes(traced.jaxpr) if "hc." in s]
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmarks", "metrics",
                           "hc_time_share.json")) as f:
        spec = json.load(f)
    # by shape, and by the scope's name where a kernel carries it
    assert spec["reader"] == "moe_readers.op_time_share"
    assert "hc\\." in spec["trace_query"]["op"]
    # bf16 streams of 128 tokens and 128 channels under the interpreter
    # seam: every connection takes the Pallas calls, under the same scopes
    from ray_tpu.ops import stream_mix
    fused = mla_moe.MlaMoeConfig.tiny(
        hc_mult=4, n_layers=4, d_model=128, dtype=jnp.bfloat16,
        **{**tiny, "vocab_size": 128})
    stream_mix.INTERPRET = True
    try:
        params = jax.eval_shape(
            lambda: mla_moe.init(fused, jax.random.PRNGKey(0)))
        before = dp.snapshot()["counters"]
        traced = jax.make_jaxpr(
            lambda p, t: mla_moe.forward_hidden(p, t, fused)[0])(
                params, jax.ShapeDtypeStruct((2, 64), jnp.int32))
        after = dp.snapshot()["counters"]
    finally:
        stream_mix.INTERPRET = False
    grew = {n: after.get(n, 0) - before.get(n, 0) for n in names}
    assert grew == {"hc.connections": 2 * 2, "hc.sinkhorn_iters": 20 * 4,
                    "hc.rows_mixed": 4 * 4 * 128, "hc.rows_fused": 4 * 4 * 128}
    entered = list(scopes(traced.jaxpr))
    assert first("hc.expand") < first("hc.pre") < first("mla.attend") \
        < first("hc.post") < first("hc.reduce")
    assert not [s for s in entered if "hc.maps" in s]


def test_the_delta_rules_plans_are_counted_and_its_gates_scoped():
    """`mixers.kda_sublayer` under both its models: a lowering with the
    kernels leaves `kda.kernels` (the forward here) and, under Solar's
    softplus gate (`kda_lower_bound` None: the any-decay plan),
    `kda.kernels_any_decay` beside it and `kda.halving_products` (6 levels
    x A's and B's rows x the grid step's rows) beside `kda.solve_products`;
    under Ling's bounded gate `kda.kernels_any_decay` stays 0 but is THERE.
    The low-rank gate projections sit under `kda.gate_lora` inside
    `kda.gates`, the channel gate under `attn.gate`: op names tell them
    from the big projections."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import hybrid_moe, solar_open2
    from ray_tpu.ops import kda as kda_op

    names = ("kda.kernels", "kda.kernels_any_decay", "kda.halving_products",
             "kda.solve_products", "kda.layers")

    def lowered(module, cfg):
        params = jax.eval_shape(lambda: module.init(cfg, jax.random.PRNGKey(0)))
        kda_op._kda_fwd_pallas.clear_cache()
        before = dp.snapshot()["counters"]
        kept = kda_op.kda
        # the kernels' branch, interpreted: what a TPU's lowering counts
        kda_op.kda = lambda *a, **kw: kept(*a, **dict(kw, interpret=True))
        try:
            traced = jax.make_jaxpr(
                lambda p, t: module.forward_hidden(p, t, cfg)[0])(
                    params, jax.ShapeDtypeStruct((1, 64), jnp.int32))
        finally:
            kda_op.kda = kept
        after = dp.snapshot()["counters"]
        return traced, {n: after.get(n, 0) - before.get(n, 0) for n in names
                        if n in after}

    def scopes(jaxpr):
        for eqn in jaxpr.eqns:
            yield str(eqn.source_info.name_stack)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scopes(sub)

    traced, grew = lowered(solar_open2, solar_open2.SolarOpen2Config.tiny(
        layers=(0, 1, 2, 3), remat=False, n_experts_held=4))
    # one scanned body of the period's three KDA layers: 4 heads a step
    assert grew == {"kda.kernels": 1, "kda.kernels_any_decay": 1,
                    "kda.halving_products": 6 * 2 * 4,
                    "kda.solve_products": 10 * 4, "kda.layers": 1}
    entered = set(scopes(traced.jaxpr))
    assert any("kda.gates/kda.gate_lora" in s for s in entered)
    assert any("attn.gate" in s for s in entered)
    traced, grew = lowered(hybrid_moe, hybrid_moe.HybridMoeConfig.tiny(
        layers=(3, 4, 5), remat=False, n_experts_held=4))
    assert grew["kda.kernels"] == 1 and grew["kda.kernels_any_decay"] == 0
    assert grew.get("kda.halving_products", 0) == 0
    assert not [s for s in scopes(traced.jaxpr) if "kda.gate_lora" in s]


@pytest.mark.parametrize("model", ["hybrid_moe", "solar_open2"])
def test_the_delta_rules_operands_are_counted_under_both_models(
        model, monkeypatch):
    """`mixers.kda_sublayer` counts, per lowering, `kda.prep_rows` (a token's
    head of q, k or v: 3 x B x S x H a layer body) and `kda.prep_rows_fused`,
    those that `ops/kda_prep.py`'s calls make: all of them at a 128-wide head
    and whole token tiles (under the interpreter's seam here, on a TPU
    otherwise), under the scope `kda.prep`; none at the
    tiny configuration's 16-wide head, where the counter is THERE and 0
    (`kda_prep_fused_share` reads 0, not nothing)."""
    import importlib

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import kda_prep

    module = importlib.import_module("ray_tpu.models." + model)
    tiny = {"hybrid_moe": lambda **kw: module.HybridMoeConfig.tiny(
                layers=(3, 4, 5), **kw),
            "solar_open2": lambda **kw: module.SolarOpen2Config.tiny(
                layers=(0, 1, 2, 3), **kw)}[model]
    monkeypatch.setattr(kda_prep, "INTERPRET", True)
    monkeypatch.setattr(kda_prep, "TOKEN_TILE", 32)
    names = ("kda.prep_rows", "kda.prep_rows_fused", "kda.layers")

    def lowered(cfg):
        params = jax.eval_shape(lambda: module.init(cfg, jax.random.PRNGKey(0)))
        before = dp.snapshot()["counters"]
        traced = jax.make_jaxpr(
            lambda p, t: module.forward_hidden(p, t, cfg)[0])(
                params, jax.ShapeDtypeStruct((2, 64), jnp.int32))
        after = dp.snapshot()["counters"]
        return traced, {n: after[n] - before.get(n, 0) for n in names}

    def scopes(jaxpr):
        for eqn in jaxpr.eqns:
            yield str(eqn.source_info.name_stack)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scopes(sub)

    wide = tiny(remat=False, n_experts_held=4, kda_head_dim=128)
    traced, grew = lowered(wide)
    rows = 3 * 2 * 64 * wide.n_heads * grew["kda.layers"]
    assert grew["kda.layers"] >= 1
    assert grew["kda.prep_rows"] == grew["kda.prep_rows_fused"] == rows
    assert any("kda.prep" in s for s in scopes(traced.jaxpr))
    traced, grew = lowered(tiny(remat=False, n_experts_held=4))
    assert grew["kda.prep_rows"] > 0 == grew["kda.prep_rows_fused"]
    assert not [s for s in scopes(traced.jaxpr) if "kda.prep" in s]
