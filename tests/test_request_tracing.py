"""End-to-end distributed request tracing (ISSUE 11).

Covers the trace-context contract end to end: W3C traceparent
parse/format, child-from-parent inheritance through nested tasks, actor
pushes (including across a restart — a requeued spec keeps its trace),
streaming-generator chunks, and proxy->router->replica over HTTP; the
TaskSpec trace-field wire roundtrip (the RTL005
spec-serialization-drift class of bug); head sampling + tail-based
force-keep promotion in the GCS span store; and the serve proxy's
X-Trace-Id/traceparent headers on success AND on every typed-refusal
path from ISSUE 9 (404 / 429 / 503 / 504).

Fast slice: `pytest -m tracing`.
"""

import asyncio
import json
import os
import time
import urllib.error
import urllib.request

import pytest

import ray_tpu
from ray_tpu._private import tracing
from ray_tpu._private.config import CONFIG
from ray_tpu._private.ids import JobID, TaskID
from ray_tpu._private.specs import (
    TaskSpec,
    TaskType,
    spec_from_wire,
    spec_to_wire,
)

pytestmark = pytest.mark.tracing


@pytest.fixture(autouse=True)
def _clean_spans():
    tracing.clear_for_tests()
    yield


# ---------------------------------------------------------------------------
# trace context: W3C header + inheritance
# ---------------------------------------------------------------------------

def test_traceparent_roundtrip():
    ctx = tracing.start_trace(sampled=True)
    header = ctx.traceparent()
    version, trace_id, span_id, flags = header.split("-")
    assert version == "00" and flags == "01"
    assert len(trace_id) == 32 and len(span_id) == 16
    parsed = tracing.parse_traceparent(header)
    assert parsed.trace_id == ctx.trace_id
    assert parsed.span_id == ctx.span_id
    assert parsed.sampled is True
    unsampled = tracing.TraceContext(ctx.trace_id, ctx.span_id,
                                     sampled=False)
    assert tracing.parse_traceparent(unsampled.traceparent()).sampled is False


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-abc-def-01",
    "00-" + "0" * 32 + "-" + "1" * 16 + "-01",   # all-zero trace id
    "00-" + "1" * 32 + "-" + "0" * 16 + "-01",   # all-zero span id
    "00-" + "z" * 32 + "-" + "1" * 16 + "-01",   # non-hex
    "00-" + "1" * 31 + "-" + "1" * 16 + "-01",   # short trace id
])
def test_traceparent_malformed(bad):
    assert tracing.parse_traceparent(bad) is None


def test_child_context_inheritance():
    root = tracing.start_trace(sampled=True)
    child = root.child()
    assert child.trace_id == root.trace_id
    assert child.parent_id == root.span_id
    assert child.span_id != root.span_id
    assert child.sampled is True


def test_context_for_submission_ambient_and_sampling():
    # no ambient, rate 0: no context at all (the zero-cost default)
    assert tracing.context_for_submission() is None
    with tracing.trace_scope(tracing.start_trace()):
        ctx = tracing.context_for_submission()
        assert ctx is not None and ctx.parent_id is not None
    # rate 1.0: every submission mints a sampled root
    CONFIG.set("trace_sample_rate", 1.0)
    try:
        ctx = tracing.context_for_submission()
        assert ctx is not None and ctx.sampled and ctx.parent_id is None
    finally:
        CONFIG.set("trace_sample_rate", 0.0)


def test_ingest_traceparent():
    incoming = tracing.start_trace(sampled=True)
    ctx = tracing.ingest_traceparent(incoming.traceparent())
    assert ctx.trace_id == incoming.trace_id
    assert ctx.parent_id == incoming.span_id  # child of the client's span
    assert ctx.sampled
    # absent/malformed: fresh root, unsampled at the default rate
    fresh = tracing.ingest_traceparent(None)
    assert fresh.trace_id != incoming.trace_id and not fresh.sampled
    assert tracing.ingest_traceparent("nonsense").sampled is False


# ---------------------------------------------------------------------------
# TaskSpec wire codec (the RTL005 spec-serialization-drift satellite)
# ---------------------------------------------------------------------------

def _spec(**kw):
    return TaskSpec(task_id=TaskID.for_normal_task(JobID.nil()),
                    job_id=JobID.nil(), task_type=TaskType.NORMAL_TASK,
                    function_id="fid", function_name="fn", **kw)


def test_spec_trace_fields_survive_the_wire():
    ctx = tracing.start_trace(sampled=True).child()
    sp = _spec(trace_ctx=ctx.to_wire())
    rt = spec_from_wire(spec_to_wire(sp))
    assert rt.trace_ctx == sp.trace_ctx
    restored = tracing.TraceContext.from_wire(rt.trace_ctx)
    assert restored.trace_id == ctx.trace_id
    assert restored.span_id == ctx.span_id
    assert restored.parent_id == ctx.parent_id
    assert restored.sampled is True
    # untraced spec stays untraced
    assert spec_from_wire(spec_to_wire(_spec())).trace_ctx is None


def test_spec_trace_fields_tolerate_old_wire_tuples():
    """A peer running the previous wire format (no trace slot) must
    decode cleanly to an untraced spec — mixed-version pushes degrade,
    never corrupt."""
    wire = spec_to_wire(_spec(trace_ctx=tracing.start_trace().to_wire()))
    old = wire[:26]  # pre-tracing tuple length
    assert spec_from_wire(old).trace_ctx is None


def test_rtl005_covers_trace_ctx():
    """The linter's spec-serialization-drift check must keep enforcing
    the new field: run RTL005 over the real specs module and assert it
    is clean (removing trace_ctx from either codec direction would fail
    CI, not a 3am debugging session)."""
    from tools.raylint.core import run_lint

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    diags = run_lint(repo_root, ["ray_tpu/_private/specs.py"],
                     select=["spec-serialization-drift"])
    assert diags == [], [d.message for d in diags]


# ---------------------------------------------------------------------------
# span buffer + rendering (pure)
# ---------------------------------------------------------------------------

def _span(trace_id, span_id, parent, name, start, end, proc="p",
          sampled=False, pid=1):
    return {"trace_id": trace_id, "span_id": span_id, "parent_id": parent,
            "name": name, "proc": proc, "pid": pid, "start": start,
            "end": end, "sampled": sampled, "attrs": {}}


def test_build_span_tree_and_format():
    spans = [
        _span("t1", "a", None, "proxy.request", 0.0, 1.0, proc="proxy"),
        _span("t1", "b", "a", "task:handler", 0.1, 0.9, proc="owner"),
        _span("t1", "c", "b", "task.execute", 0.3, 0.8, proc="worker"),
        # orphan: parent never flushed — must root itself, not vanish
        _span("t1", "d", "missing", "raylet.lease", 0.2, 0.25),
    ]
    roots = tracing.build_span_tree(spans)
    assert len(roots) == 2
    by_name = {r["span"]["name"]: r for r in roots}
    tree = by_name["proxy.request"]
    assert tree["children"][0]["span"]["name"] == "task:handler"
    assert tree["children"][0]["children"][0]["span"]["name"] == \
        "task.execute"
    text = tracing.format_trace(spans)
    assert "proxy.request" in text and "raylet.lease" in text
    assert "3 process(es)" not in text  # 4 distinct procs: p/proxy/owner/worker
    assert "4 process(es)" in text


def test_trace_chrome_flow_events_link_processes():
    spans = [
        _span("t1", "a", None, "proxy.request", 0.0, 1.0, proc="proxy"),
        _span("t1", "b", "a", "task.execute", 0.2, 0.9, proc="worker"),
        _span("t1", "c", "b", "inner", 0.3, 0.4, proc="worker"),
    ]
    trace = tracing.trace_chrome(spans)
    slices = [e for e in trace if e["ph"] == "X"]
    assert len(slices) == 3
    # one s/f flow pair for the cross-process edge, none for same-process
    starts = [e for e in trace if e["ph"] == "s"]
    finishes = [e for e in trace if e["ph"] == "f"]
    assert len(starts) == 1 and len(finishes) == 1
    assert starts[0]["pid"] == "proxy" and finishes[0]["pid"] == "worker"


def test_record_span_guards_and_ids():
    assert tracing.record_span("x", None, 0.0, 1.0) is None  # cheap no-op
    ctx = tracing.start_trace(sampled=True)
    sid = tracing.record_span("stage", ctx.to_wire(), 0.0, 1.0)
    spans = tracing.get_local_spans()
    rec = next(s for s in spans if s["span_id"] == sid)
    # default: fresh span parented at the context's span
    assert rec["parent_id"] == ctx.span_id and rec["sampled"] is True
    own = tracing.record_span("root", ctx, 0.0, 1.0, span_id=ctx.span_id)
    rec = next(s for s in tracing.get_local_spans() if s["span_id"] == own)
    assert rec["parent_id"] == ctx.parent_id  # the context's own span


def test_force_trace_dedupes_and_emits_event():
    from ray_tpu._private import event_log

    event_log.clear_for_tests()
    tracing.force_trace("t" * 32, "unit_test")
    tracing.force_trace("t" * 32, "unit_test")  # dedup window
    tracing.force_trace(None, "noop")           # cheap no-op
    forced = [e for e in event_log.recent(100, etype="trace.force")
              if e.get("trace_id") == "t" * 32]
    assert len(forced) == 1
    assert forced[0]["data"]["reason"] == "unit_test"


# ---------------------------------------------------------------------------
# GCS span store: tail-based promotion
# ---------------------------------------------------------------------------

def _run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


def test_span_store_tail_promotion():
    from ray_tpu.gcs.server import GcsSpanManager

    mgr = GcsSpanManager(max_spans=1000, provisional_max=1000)
    # unsampled spans park in the provisional tier
    mgr.add_local([_span("tA", "a1", None, "task:x", 0.0, 1.0)], [], None)
    assert _run(mgr.handle_get_span_stats({}))["provisional"] == 1
    summaries = _run(mgr.handle_list_traces({}))
    assert summaries == []  # provisional traces are not listed
    # ...but the trace is still inspectable (a just-served request)
    got = _run(mgr.handle_get_trace({"trace_id": "tA"}))
    assert len(got["spans"]) == 1 and got["forced"] is False
    # a force marker promotes the parked spans...
    mgr.add_local([], [("tA", "task_error:Boom")], None)
    got = _run(mgr.handle_get_trace({"trace_id": "tA"}))
    assert got["forced"] and got["forced_reason"] == "task_error:Boom"
    stats = _run(mgr.handle_get_span_stats({}))
    assert stats["provisional"] == 0 and stats["spans"] == 1
    # ...and LATE-arriving unsampled spans of a forced trace go durable
    mgr.add_local([_span("tA", "a2", "a1", "task.reply", 1.0, 1.1)],
                  [], None)
    assert _run(mgr.handle_get_span_stats({}))["spans"] == 2
    # sampled spans go durable immediately and are listed
    mgr.add_local([_span("tB", "b1", None, "proxy.request", 2.0, 3.0,
                         sampled=True)], [], None)
    rows = _run(mgr.handle_list_traces({}))
    assert {r["trace_id"] for r in rows} == {"tA", "tB"}
    root = next(r for r in rows if r["trace_id"] == "tB")
    assert root["root"] == "proxy.request" and root["spans"] == 1
    # client-originated trace: NO stored span is parentless (the proxy's
    # span is a child of the client's own span id) — the listing must
    # still name a root via the parent-not-stored rule
    mgr.add_local([_span("tD", "d1", "client-span", "proxy.request",
                         4.0, 5.0, sampled=True)], [], None)
    rows = _run(mgr.handle_list_traces({}))
    ext = next(r for r in rows if r["trace_id"] == "tD")
    assert ext["root"] == "proxy.request"


def test_span_store_dedupes_get_trace():
    from ray_tpu.gcs.server import GcsSpanManager

    mgr = GcsSpanManager()
    span = _span("tC", "c1", None, "task:x", 0.0, 1.0)
    mgr.add_local([span], [], None)
    mgr.add_local([dict(span, sampled=True)], [], None)
    got = _run(mgr.handle_get_trace({"trace_id": "tC"}))
    assert len(got["spans"]) == 1


def test_latency_p99_breach_forces_trace(monkeypatch):
    from ray_tpu._private import latency

    forced = []
    monkeypatch.setattr(tracing, "force_trace",
                        lambda tid, reason: forced.append((tid, reason)))
    # fresh windows: a full-suite run leaves real (sometimes seconds-
    # long) stage samples behind, which would mask the outlier
    for window in latency._stage_window.values():
        window.clear()
    fast = {s: 0.0001 for s in latency.STAGES}
    for _ in range(latency._P99_MIN_SAMPLES + 8):
        latency._record_one("tid", "fn", "NORMAL_TASK", fast)
    slow = dict(fast, execute=0.5)
    latency._record_one("tid2", "fn", "NORMAL_TASK", slow,
                        trace_id="f" * 32)
    assert any(t == "f" * 32 and "latency_p99_breach" in r
               for t, r in forced)


# ---------------------------------------------------------------------------
# cluster e2e: inheritance through tasks / actors / generators
# ---------------------------------------------------------------------------

def _get_trace(trace_id, min_spans=1, timeout=15.0, require_names=()):
    """Flush local spans and poll the GCS store until the trace shows.
    Span count alone is NOT a completeness signal — each process flushes
    on its own ~1s cadence, so a replica can land 8 spans while the
    proxy's are still in flight; callers that assert specific span names
    must pass them as `require_names` so the poll waits for all of
    them."""
    cw = ray_tpu._raylet.get_core_worker()
    tracing.flush_spans(timeout=2.0)
    deadline = time.monotonic() + timeout
    reply = {}
    while time.monotonic() < deadline:
        reply = cw._gcs.call("get_trace", {"trace_id": trace_id})
        spans = reply.get("spans") or []
        names = {s["name"] for s in spans}
        if len(spans) >= min_spans and set(require_names) <= names:
            return reply
        time.sleep(0.2)
    return reply


def test_nested_task_trace_inheritance(ray_start_regular):
    @ray_tpu.remote
    def child(x):
        return x + 1

    @ray_tpu.remote
    def parent(x):
        return ray_tpu.get(child.remote(x)) + 10

    root = tracing.start_trace(sampled=True)
    with tracing.trace_scope(root):
        assert ray_tpu.get(parent.remote(1)) == 12
    reply = _get_trace(root.trace_id, min_spans=10,
                       require_names=("task:parent", "task:child",
                                      "raylet.lease", "task.execute"))
    spans = reply["spans"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # the task tree: both tasks' root spans, linked child-under-parent
    parent_span = by_name["task:parent"][0]
    child_span = by_name["task:child"][0]
    assert parent_span["parent_id"] == root.span_id
    assert child_span["parent_id"] == parent_span["span_id"]
    # owner + raylet + worker all contributed
    assert "raylet.lease" in by_name
    assert "task.execute" in by_name
    assert len({s["pid"] for s in spans}) >= 2  # cross-process
    # every span of this trace shares the id
    assert all(s["trace_id"] == root.trace_id for s in spans)


def test_task_events_and_breakdowns_carry_trace_id(ray_start_regular):
    @ray_tpu.remote
    def traced():
        return 1

    root = tracing.start_trace(sampled=True)
    with tracing.trace_scope(root):
        ray_tpu.get(traced.remote())
    from ray_tpu._private import latency

    entry = next(e for e in reversed(latency.recent(200))
                 if e.get("name") == "traced")
    assert entry["trace_id"] == root.trace_id
    # terminal task events (the `ray-tpu latency`/timeline feed) too
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        from ray_tpu.util.state import list_tasks

        evs = [e for e in list_tasks(limit=100_000, raw_events=True)
               if e.get("trace_id") == root.trace_id]
        if evs:
            break
        time.sleep(0.2)
    assert evs, "no task events carried the trace id"


def test_actor_trace_inheritance_across_restart(ray_start_regular):
    @ray_tpu.remote(max_restarts=1)
    class Counter:
        def __init__(self):
            self.n = 0

        def pid(self):
            return os.getpid()

        def bump(self):
            self.n += 1
            return self.n

    root = tracing.start_trace(sampled=True)
    with tracing.trace_scope(root):
        c = Counter.remote()
        assert ray_tpu.get(c.bump.remote()) == 1
        pid = ray_tpu.get(c.pid.remote())
        os.kill(pid, 9)
        # the restarted incarnation serves calls from the SAME trace —
        # requeued/retried specs keep their context
        deadline = time.monotonic() + 30
        while True:
            try:
                assert ray_tpu.get(c.bump.remote(), timeout=10) >= 1
                break
            except Exception:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.5)
    reply = _get_trace(root.trace_id, min_spans=4,
                       require_names=("gcs.actor_admission", "task:bump"))
    names = {s["name"] for s in reply["spans"]}
    assert "gcs.actor_admission" in names
    assert "task:bump" in names
    bump_spans = [s for s in reply["spans"] if s["name"] == "task:bump"]
    assert all(s["trace_id"] == root.trace_id for s in bump_spans)
    assert len(bump_spans) >= 2  # before and after the restart


def test_streaming_generator_chunk_spans(ray_start_regular):
    @ray_tpu.remote
    def inner():
        return "leaf"

    @ray_tpu.remote
    def stream(n):
        # a nested submission INSIDE the generator body inherits too
        ray_tpu.get(inner.remote())
        for i in range(int(n)):
            yield i

    root = tracing.start_trace(sampled=True)
    with tracing.trace_scope(root):
        gen = stream.options(num_returns="streaming").remote(3)
        items = [ray_tpu.get(r) for r in gen]
    assert items == [0, 1, 2]
    reply = _get_trace(root.trace_id, min_spans=6,
                       require_names=("task.stream_item", "task:inner"))
    by_name = {}
    for s in reply["spans"]:
        by_name.setdefault(s["name"], []).append(s)
    chunks = by_name.get("task.stream_item", [])
    assert len(chunks) == 3
    assert sorted(c["attrs"]["index"] for c in chunks) == [0, 1, 2]
    assert "task:inner" in by_name  # nested-from-generator inheritance


def test_default_rate_leaves_plain_tasks_untraced(ray_start_regular):
    @ray_tpu.remote
    def plain():
        return 1

    before = tracing.local_span_stats()["recorded"]
    assert ray_tpu.get(plain.remote()) == 1
    cw = ray_tpu._raylet.get_core_worker()
    # the spec itself carries no context...
    spec = cw._pending_tasks.get("nope", None)  # no pending leftovers
    assert spec is None
    # ...and no TRACE spans were recorded owner-side (profile spans from
    # the latency stage lane are local-only and don't count)
    after = tracing.local_span_stats()["recorded"]
    assert after == before


def test_unsampled_error_is_force_kept(ray_start_regular):
    @ray_tpu.remote(max_retries=0)
    def boom():
        raise ValueError("kaboom")

    root = tracing.start_trace(sampled=False)  # head sampling said no
    with tracing.trace_scope(root):
        with pytest.raises(Exception):
            ray_tpu.get(boom.remote())
    reply = _get_trace(root.trace_id, min_spans=1)
    deadline = time.monotonic() + 10
    while not reply.get("forced") and time.monotonic() < deadline:
        time.sleep(0.2)
        reply = _get_trace(root.trace_id, min_spans=1)
    assert reply["forced"], reply
    assert "task_error" in (reply["forced_reason"] or "")
    # the trace.force event cross-references the same id
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        from ray_tpu.util.state import trace_events

        evs = trace_events(root.trace_id)
        if any(e["type"] == "trace.force" for e in evs):
            break
        time.sleep(0.2)
    assert any(e["type"] == "trace.force" for e in evs)


# ---------------------------------------------------------------------------
# serve e2e: headers on every path + the cross-process span tree
# ---------------------------------------------------------------------------

@pytest.fixture
def serve_shutdown():
    yield
    try:
        from ray_tpu import serve

        serve.shutdown()
    except Exception:
        pass


def _request(url, headers=None, timeout=30):
    req = urllib.request.Request(url, headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def test_proxy_trace_headers_and_span_tree(ray_start_regular,
                                           serve_shutdown):
    from ray_tpu import serve
    from ray_tpu._private.rpc import find_free_port

    @serve.deployment
    def app(arg):
        return {"ok": True}

    port = find_free_port()
    serve.run(app.bind(), name="traced", route_prefix="/traced",
              http_port=port)
    incoming = tracing.start_trace(sampled=True)
    status, headers, _ = _request(
        f"http://127.0.0.1:{port}/traced",
        headers={"traceparent": incoming.traceparent()})
    assert status == 200
    # the client's trace id comes back on the response, both forms
    assert headers.get("X-Trace-Id") == incoming.trace_id
    echoed = tracing.parse_traceparent(headers.get("traceparent"))
    assert echoed is not None and echoed.trace_id == incoming.trace_id
    reply = _get_trace(incoming.trace_id, min_spans=6,
                       require_names=("proxy.request", "router.pick",
                                      "task.execute"))
    spans = reply["spans"]
    names = {s["name"] for s in spans}
    assert {"proxy.request", "router.pick", "task.execute"} <= names
    procs = {s["proc"] for s in spans}
    assert len(procs) >= 3, procs  # proxy + owner shard + replica worker
    proxy_span = next(s for s in spans if s["name"] == "proxy.request")
    # the proxy span is a child of the client's span
    assert proxy_span["parent_id"] == incoming.span_id
    # and renders as one tree
    text = tracing.format_trace(spans)
    assert "proxy.request" in text


def test_proxy_generates_context_when_absent(ray_start_regular,
                                             serve_shutdown):
    from ray_tpu import serve
    from ray_tpu._private.rpc import find_free_port

    @serve.deployment
    def app2(arg):
        return "ok"

    port = find_free_port()
    serve.run(app2.bind(), name="gen_ctx", route_prefix="/gen_ctx",
              http_port=port)
    status, headers, _ = _request(f"http://127.0.0.1:{port}/gen_ctx")
    assert status == 200
    tid = headers.get("X-Trace-Id")
    assert tid and len(tid) == 32
    # at the default sample rate the generated context is unsampled, but
    # the spans are still inspectable from the provisional tier
    reply = _get_trace(tid, min_spans=1)
    assert reply["spans"] and reply["forced"] is False


def test_trace_headers_on_typed_refusal_paths(ray_start_regular,
                                              serve_shutdown):
    """Every typed-refusal path from ISSUE 9 must carry the trace id:
    404 (no route), 504 (expired deadline, X-Typed-Shed), 503
    (RetryLaterError), 429 (LLM shed) and 500 (application error)."""
    from ray_tpu import serve
    from ray_tpu._private.rpc import find_free_port
    from ray_tpu.exceptions import RetryLaterError
    from ray_tpu.serve.llm.engine import LLMOverloadedError

    @serve.deployment
    def refusals(arg):
        mode = (arg or {}).get("mode")
        if mode == "shed":
            raise RetryLaterError("queue full", retry_after_s=0.5,
                                  layer="test")
        if mode == "llm":
            raise LLMOverloadedError("llm backlog full")
        raise RuntimeError("app error")

    port = find_free_port()
    serve.run(refusals.bind(), name="refusals", route_prefix="/refuse",
              http_port=port)
    base = f"http://127.0.0.1:{port}"

    # 404: no matching route
    status, headers, _ = _request(f"{base}/no_such_route")
    assert status == 404 and len(headers.get("X-Trace-Id", "")) == 32

    # 504 up front: the deadline already passed (typed shed)
    status, headers, _ = _request(
        f"{base}/refuse", headers={"X-Request-Timeout-S": "0"})
    assert status == 504
    assert headers.get("X-Typed-Shed") == "deadline"
    assert len(headers.get("X-Trace-Id", "")) == 32

    # 503: typed bounded-queue pushback, Retry-After preserved
    status, headers, _ = _request(f"{base}/refuse?mode=shed")
    assert status == 503
    assert headers.get("Retry-After") is not None
    assert len(headers.get("X-Trace-Id", "")) == 32

    # 429: LLM overload shed
    status, headers, _ = _request(f"{base}/refuse?mode=llm")
    assert status == 429
    assert len(headers.get("X-Trace-Id", "")) == 32

    # 500: application error — and the trace is force-kept, so the
    # user-visible failure is traceable at the default sample rate
    status, headers, _ = _request(f"{base}/refuse")
    assert status == 500
    tid = headers.get("X-Trace-Id")
    assert tid and len(tid) == 32
    reply = _get_trace(tid, min_spans=1)
    deadline = time.monotonic() + 10
    while not reply.get("forced") and time.monotonic() < deadline:
        time.sleep(0.2)
        reply = _get_trace(tid, min_spans=1)
    assert reply["forced"], reply


def test_llm_trace_spans_proxy_router_replica_engine(ray_start_regular,
                                                     serve_shutdown):
    """The acceptance-criterion tree: a traced serve.llm request shows
    spans from the proxy, the router pick, the replica's streaming task
    and the engine (admission + per-decode-chunk), all under one trace
    id that also rides the SSE response headers."""
    from ray_tpu import serve
    from ray_tpu._private.rpc import find_free_port
    from ray_tpu.serve.llm import build_llm_app

    def build():
        class StubEngine:
            """serve_stream stub: 4 tokens per request, no JAX."""

            max_batch = 4
            free_slots = list(range(4))

            def serve_stream(self, feed, gen):
                while True:
                    new, _cancelled, stop = feed(True)
                    for req_id, *_ in new:
                        for i in range(4):
                            yield req_id, 7, i == 3
                    if stop:
                        return

        return StubEngine()

    app = build_llm_app(build, name="llm_traced", num_replicas=1,
                        default_config={"max_new_tokens": 4})
    port = find_free_port()
    serve.run(app, name="llm_traced", route_prefix="/llm_traced",
              http_port=port)
    incoming = tracing.start_trace(sampled=True)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/llm_traced",
        data=json.dumps({"prompt": [1, 2, 3]}).encode(),
        headers={"traceparent": incoming.traceparent()})
    with urllib.request.urlopen(req, timeout=60) as r:
        assert r.status == 200
        assert r.headers.get("X-Trace-Id") == incoming.trace_id
        body = r.read().decode()
    assert "[DONE]" in body
    reply = _get_trace(incoming.trace_id, min_spans=8, timeout=20,
                       require_names=("proxy.request", "router.pick",
                                      "engine.admission",
                                      "engine.decode_chunk",
                                      "task.stream_item"))
    spans = reply["spans"]
    names = {s["name"] for s in spans}
    assert {"proxy.request", "router.pick", "engine.admission",
            "engine.decode_chunk", "task.stream_item"} <= names, names
    assert len({s["pid"] for s in spans}) >= 2  # proxy + engine replica


def test_cli_trace_renders_tree(ray_start_regular, serve_shutdown,
                                capsys):
    from ray_tpu import serve
    from ray_tpu._private.rpc import find_free_port
    from ray_tpu.scripts.scripts import cmd_trace

    @serve.deployment
    def cli_app(arg):
        return "ok"

    port = find_free_port()
    serve.run(cli_app.bind(), name="cli_app", route_prefix="/cli",
              http_port=port)
    incoming = tracing.start_trace(sampled=True)
    status, headers, _ = _request(
        f"http://127.0.0.1:{port}/cli",
        headers={"traceparent": incoming.traceparent()})
    assert status == 200
    _get_trace(incoming.trace_id, min_spans=4,
               require_names=("proxy.request",))

    class Args:
        address = None
        trace_id = incoming.trace_id
        list = False
        json = False
        chrome = None
        limit = 50

    assert cmd_trace(Args()) == 0
    out = capsys.readouterr().out
    assert incoming.trace_id in out
    assert "proxy.request" in out
    # chrome export
    out_path = f"/tmp/trace_{incoming.trace_id[:8]}.json"

    class ChromeArgs(Args):
        chrome = out_path

    assert cmd_trace(ChromeArgs()) == 0
    with open(out_path) as f:
        trace = json.load(f)
    assert any(e.get("ph") == "s" for e in trace)  # flow arrows
    os.unlink(out_path)
