"""Overload protection (ISSUE 9): deadlines, bounded queues, retry
budgets.

Fast slice (`pytest -m overload`): deadline inheritance math and wire
re-anchoring, expired-at-pop drops at every layer (owner pump, raylet
lease queue, worker executor), typed RetryLaterError pushback from the
bounded queues with AIMD pacing, retry-budget token buckets, and
backoff-module parity with the three hand-rolled call sites it replaced
(raylet heartbeat reconnect, owner lease re-ask, actor-push requeue).

Slow tier: a chaos brownout (PR 3 error rules on the actor push path)
run twice — retry budgets on vs off — asserting the budget bounds retry
amplification (the anti-retry-storm property the overload_storm drill
exercises at the cluster level).
"""

import os
import random
import time

import pytest

import ray_tpu
from ray_tpu._private import backoff as bk
from ray_tpu._private import deadlines as dl
from ray_tpu._private.config import CONFIG
from ray_tpu.exceptions import DeadlineExceededError, RetryLaterError

pytestmark = pytest.mark.overload


# ------------------------------------------------------ deadline math


def test_effective_deadline_sources_earliest_wins():
    now = 1000.0
    # explicit only
    assert dl.effective_deadline(5.0, None, now=now) == now + 5.0
    # parent only
    assert dl.effective_deadline(None, now + 2.0, now=now) == now + 2.0
    # both: earliest wins (child may not outlive the parent's budget)
    assert dl.effective_deadline(5.0, now + 2.0, now=now) == now + 2.0
    assert dl.effective_deadline(1.0, now + 2.0, now=now) == now + 1.0
    # nothing constrains
    assert dl.effective_deadline(None, None, now=now) is None


def test_ambient_deadline_scoping():
    now = time.time()
    with dl.ambient_deadline(now + 10.0):
        got = dl.effective_deadline(None, None)
        assert got == pytest.approx(now + 10.0, abs=0.01)
        # nested tighter scope wins; outer restored after
        with dl.ambient_deadline(now + 1.0):
            assert dl.effective_deadline(None, None) == pytest.approx(
                now + 1.0, abs=0.01)
        assert dl.effective_deadline(None, None) == pytest.approx(
            now + 10.0, abs=0.01)
    assert dl.effective_deadline(None, None) is None
    # a LOOSER nested scope must not extend the outer budget
    with dl.ambient_deadline(now + 1.0):
        with dl.ambient_deadline(now + 50.0):
            assert dl.effective_deadline(None, None) == pytest.approx(
                now + 1.0, abs=0.01)


def test_deadline_rides_the_wire_as_remaining_time():
    from ray_tpu._private.ids import JobID, TaskID
    from ray_tpu._private.specs import (
        TaskSpec, TaskType, spec_from_wire, spec_to_wire)

    job = JobID.nil()
    spec = TaskSpec(
        task_id=TaskID.for_normal_task(job), job_id=job,
        task_type=TaskType.NORMAL_TASK, function_id="f",
        function_name="f", deadline_s=time.time() + 30.0)
    wire = spec_to_wire(spec)
    # the wire carries REMAINING seconds, not an absolute instant
    # (slot 25; ISSUE 11 appended the trace context after it)
    assert wire[25] == pytest.approx(30.0, abs=1.0)
    back = spec_from_wire(wire)
    assert back.deadline_s == pytest.approx(spec.deadline_s, abs=1.0)
    # no deadline stays no deadline
    spec.deadline_s = None
    assert spec_from_wire(spec_to_wire(spec)).deadline_s is None


def test_expired_and_remaining():
    assert not dl.expired(None)
    assert dl.expired(time.time() - 1.0)
    assert not dl.expired(time.time() + 60.0)
    assert dl.remaining_s(None) is None
    assert dl.remaining_s(time.time() + 10.0) == pytest.approx(10.0,
                                                              abs=0.5)


# ------------------------------------------------- backoff primitives


def test_backoff_policy_heartbeat_parity():
    """The policy module reproduces the PR 3 heartbeat-reconnect schedule
    bit for bit: same seeded rng in, same delays out."""
    period, max_s, jitter, seed = 0.25, 5.0, 0.5, b"node-seed"
    ref_rng = random.Random(seed)
    expected = []
    for failures in range(1, 12):
        base = min(period * (2 ** min(failures, 10)), max_s)
        expected.append(base * (1.0 - jitter * ref_rng.random()))
    policy = bk.BackoffPolicy(base_s=period, multiplier=2.0, max_s=max_s,
                              jitter=jitter, rng=random.Random(seed))
    got = [policy.delay(n) for n in range(1, 12)]
    assert got == pytest.approx(expected)


def test_backoff_policy_basics():
    p = bk.BackoffPolicy(base_s=0.2, multiplier=2.0, max_s=1.0)
    assert p.delay(0) == 0.0
    assert p.delay(1) == pytest.approx(0.4)
    assert p.delay(2) == pytest.approx(0.8)
    assert p.delay(10) == 1.0  # capped
    assert p.delay(100) == 1.0  # exponent capped, no overflow


def test_replaced_call_sites_route_through_the_module():
    """The three hand-rolled retry-policy copies are gone: heartbeat
    reconnect, owner lease re-ask and the GCS actor scheduler all build
    their delays from _private/backoff (and the pushback paths pace with
    its AIMDPacer)."""
    import inspect

    from ray_tpu.gcs import actor_manager
    from ray_tpu.raylet import raylet
    from ray_tpu.worker import core_worker

    hb = inspect.getsource(raylet.Raylet._heartbeat_loop)
    assert "_reconnect_policy.delay" in hb
    assert "2 **" not in hb  # the inline formula is gone
    lease = inspect.getsource(core_worker.CoreWorker._request_lease_inner)
    assert "BackoffPolicy" in lease and "pacer.on_pushback" in lease
    assert "sleep(0.2)" not in lease and "sleep(0.1)" not in lease
    sched = inspect.getsource(actor_manager.GcsActorManager._schedule_actor)
    assert "BackoffPolicy" in sched and "AIMDPacer" in sched
    push = inspect.getsource(core_worker.CoreWorker._on_actor_push_failure)
    assert "default_retry_budget" in push


def test_aimd_pacer():
    p = bk.AIMDPacer(base_s=0.1, multiplier=2.0, decrease_s=0.15,
                     max_s=2.0)
    assert p.delay_s == 0.0
    assert p.on_pushback() == pytest.approx(0.1)       # starts at base
    assert p.on_pushback() == pytest.approx(0.2)       # multiplicative up
    assert p.on_pushback(hint_s=1.5) == pytest.approx(1.5)  # hint floors
    assert p.on_pushback() == pytest.approx(2.0)       # capped
    assert p.on_success() == pytest.approx(1.85)       # additive down
    for _ in range(20):
        p.on_success()
    assert p.delay_s == 0.0  # fully recovered, never negative


def test_retry_budget_token_bucket():
    b = bk.RetryBudget(capacity=3.0, fill_per_s=10.0)
    t0 = 100.0
    for _ in range(3):
        assert b.try_spend("peer", "m", now=t0)
    assert not b.try_spend("peer", "m", now=t0)  # dry: fail fast
    # distinct (peer, method) keys have their own buckets
    assert b.try_spend("other", "m", now=t0)
    assert b.try_spend("peer", "n", now=t0)
    # refill at fill_per_s, capped at capacity
    assert b.try_spend("peer", "m", now=t0 + 0.2)  # 2 tokens refilled
    assert b.tokens("peer", "m", now=t0 + 100.0) == 3.0
    # disabled budgets always grant (the brownout-comparison mode)
    off = bk.RetryBudget(capacity=1.0, fill_per_s=0.0, enabled=False)
    assert all(off.try_spend("p", "m", now=t0) for _ in range(50))


# ------------------------------------------------ expired-at-pop e2e


@pytest.fixture
def overload_cluster():
    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


def _fresh_fn(tag, value):
    def fn():
        return value
    fn.__name__ = f"overload_{tag}_{value}"
    return ray_tpu.remote(fn)


def test_expired_work_dropped_at_queue_pop(overload_cluster):
    """Doomed-work elimination: a task whose deadline passes while it
    queues is dropped at pop with a typed error — and the drop leaves a
    task.deadline_expired event in the cluster log."""
    from ray_tpu._private import event_log
    from ray_tpu.util.state import list_cluster_events

    @ray_tpu.remote
    def blocker():
        time.sleep(0.6)

    blockers = [blocker.remote() for _ in range(6)]
    doomed = _fresh_fn("doomed", 1).options(deadline_s=0.1).remote()
    with pytest.raises(DeadlineExceededError):
        ray_tpu.get(doomed, timeout=20)
    ray_tpu.get(blockers)
    event_log.flush(timeout=2.0)
    evs = list_cluster_events(etype="task.deadline_expired", limit=100)
    assert evs, "no task.deadline_expired event recorded"
    assert all((e.get("data") or {}).get("layer") in
               ("owner", "raylet", "worker") for e in evs)


def test_expired_drop_is_never_retried(overload_cluster):
    """A worker-layer deadline drop rides the error-reply shape, but it
    must NOT consume retry_exceptions retries: the requeued spec would
    keep its already-expired absolute deadline, so every retry is a
    guaranteed futile lease+push round trip (retry amplification of
    doomed work — the review find on ISSUE 11)."""
    from ray_tpu._private import event_log
    from ray_tpu.util.state import list_cluster_events

    @ray_tpu.remote
    def blocker():
        time.sleep(0.6)

    blockers = [blocker.remote() for _ in range(6)]
    doomed = _fresh_fn("retried_doomed", 1).options(
        deadline_s=0.1, retry_exceptions=True, max_retries=3).remote()
    with pytest.raises(DeadlineExceededError):
        ray_tpu.get(doomed, timeout=20)
    ray_tpu.get(blockers)
    event_log.flush(timeout=2.0)
    task_hex = doomed.object_id().task_id().hex()
    retries = [e for e in list_cluster_events(etype="task.retry",
                                              task_id=task_hex,
                                              limit=100)]
    assert retries == [], retries


def test_actor_call_expired_at_worker_pop(overload_cluster):
    @ray_tpu.remote
    class A:
        def work(self, v):
            time.sleep(0.3)
            return v

    a = A.remote()
    assert ray_tpu.get(a.work.remote(0), timeout=30) == 0
    busy = a.work.remote(1)          # occupies the ordered actor
    doomed = a.work.options(deadline_s=0.05).remote(2)
    with pytest.raises(DeadlineExceededError):
        ray_tpu.get(doomed, timeout=20)
    # the expired call advanced the sequencing gate: later calls proceed
    assert ray_tpu.get(busy, timeout=20) == 1
    assert ray_tpu.get(a.work.remote(3), timeout=20) == 3


def test_deadline_inherited_by_child_tasks(overload_cluster):
    """A child task submitted inside a running task carries the parent's
    remaining budget on its spec (a child of doomed work is doomed)."""

    @ray_tpu.remote
    def child_deadline():
        from ray_tpu._raylet import get_core_worker

        cw = get_core_worker()
        return cw.current_spec().deadline_s

    @ray_tpu.remote
    def parent():
        return ray_tpu.get(child_deadline.remote(), timeout=30)

    before = time.time()
    got = ray_tpu.get(parent.options(deadline_s=25.0).remote(), timeout=60)
    assert got is not None
    assert before < got <= before + 25.5
    # no deadline anywhere -> children unconstrained
    assert ray_tpu.get(parent.remote(), timeout=60) is None


# --------------------------------------------- typed pushback + AIMD


def test_actor_mailbox_bound_typed_pushback(overload_cluster):
    prev = CONFIG.actor_mailbox_max
    CONFIG.set("actor_mailbox_max", 5)
    try:
        @ray_tpu.remote
        class SlowStart:
            def __init__(self):
                time.sleep(0.8)

            def m(self):
                return 1

        s = SlowStart.remote()
        refs, shed = [], None
        for _ in range(50):
            try:
                refs.append(s.m.remote())
            except RetryLaterError as e:
                shed = e
                break
        assert shed is not None, "mailbox never pushed back"
        assert shed.layer == "actor_mailbox"
        assert shed.retry_after_s > 0
        # accepted calls still complete (shed, never lost)
        assert ray_tpu.get(refs, timeout=30) == [1] * len(refs)
        # slots freed: submission works again
        assert ray_tpu.get(s.m.remote(), timeout=30) == 1
    finally:
        CONFIG.set("actor_mailbox_max", prev)


def test_lease_queue_bound_sheds_and_paces(overload_cluster):
    """Overflowing the raylet lease queue returns typed retry_later; the
    owner paces resubmission (AIMD) and every task still completes."""
    from ray_tpu._private import event_log
    from ray_tpu.util.state import list_cluster_events

    prev = CONFIG.raylet_lease_queue_max
    CONFIG.set("raylet_lease_queue_max", 4)
    try:
        @ray_tpu.remote
        def blocker():
            time.sleep(0.5)

        blockers = [blocker.remote() for _ in range(4)]
        # distinct scheduling keys: each needs its own lease ask
        fns = [_fresh_fn("shed", i) for i in range(12)]
        out = ray_tpu.get([fn.remote() for fn in fns], timeout=90)
        assert sorted(out) == list(range(12))
        ray_tpu.get(blockers)
        event_log.flush(timeout=2.0)
        evs = list_cluster_events(etype="task.shed", limit=200)
        assert any((e.get("data") or {}).get("layer") == "raylet"
                   for e in evs), "no raylet-layer task.shed recorded"
    finally:
        CONFIG.set("raylet_lease_queue_max", prev)


def test_gcs_creation_queue_bound(overload_cluster):
    prev = CONFIG.gcs_actor_creation_queue_max
    CONFIG.set("gcs_actor_creation_queue_max", 2)
    try:
        @ray_tpu.remote
        class SlowInit:
            def __init__(self):
                time.sleep(1.0)

            def ping(self):
                return True

        first = [SlowInit.remote() for _ in range(2)]
        deadline = time.monotonic() + 20.0
        shed = None
        while time.monotonic() < deadline and shed is None:
            try:
                SlowInit.options(name=f"named_{time.monotonic()}").remote()
                time.sleep(0.05)
            except RetryLaterError as e:
                shed = e
        assert shed is not None, "creation queue never pushed back"
        assert shed.layer == "gcs_actor_creation"
        # the accepted actors still come up
        assert ray_tpu.get([a.ping.remote() for a in first], timeout=60)
    finally:
        CONFIG.set("gcs_actor_creation_queue_max", prev)


def test_serve_proxy_maps_deadline_header(overload_cluster, tmp_path):
    """X-Request-Timeout-S becomes a task deadline: a request whose
    budget expires is refused typed (504 = shed), not hung or lost."""
    import http.client

    from ray_tpu import serve
    from ray_tpu._private.rpc import find_free_port

    budget_s = 0.2
    # The handler outlasts the tight request's budget by 0.8 s counted from
    # its OWN entry, which the test observes before it sends that request:
    # nothing here assumes how fast this machine gets a request into the
    # replica. (The proxy stops waiting 1.0 s past the budget.)
    hold_s = budget_s + 0.8
    entered = str(tmp_path / "entered")

    @serve.deployment(max_ongoing_requests=1)
    def slow_echo(body=None):
        with open(entered, "a") as f:
            f.write(".")
        time.sleep(hold_s)
        return {"ok": True}

    port = find_free_port()
    # ONE proxy shard: the replica starts one caller's calls in order, and
    # each shard is a caller of its own. Calls from two shards run side by
    # side in a max_ongoing_requests=1 replica (ROADMAP D10), so the tight
    # request would be served, not queued, whenever the kernel handed its
    # connection to another shard than the blocker's.
    serve.run(slow_echo.bind(), name="overload_app", http_port=port,
              http_shards=1)
    try:
        def req(headers):
            conn = http.client.HTTPConnection(f"127.0.0.1:{port}",
                                              timeout=30)
            try:
                conn.request("GET", "/overload_app", headers=headers)
                resp = conn.getresponse()
                resp.read()
                return resp.status
            finally:
                conn.close()

        assert req({}) == 200
        # a generous budget passes
        assert req({"X-Request-Timeout-S": "30"}) == 200
        # an already-absurd budget is refused up front
        assert req({"X-Request-Deadline": f"{time.time() - 1:.3f}"}) == 504
        # a budget shorter than the queue wait is dropped at queue-pop:
        # fill the single-ongoing replica, then send a tight request
        import threading

        calls_before = os.path.getsize(entered)
        t = threading.Thread(target=req, args=({},), daemon=True)
        t.start()
        give_up = time.monotonic() + 30
        while os.path.getsize(entered) == calls_before:
            assert time.monotonic() < give_up, "request never reached replica"
            time.sleep(0.005)
        status = req({"X-Request-Timeout-S": str(budget_s)})
        assert status == 504, status
        t.join(timeout=10)
    finally:
        serve.shutdown()


# --------------------------------------------- chaos brownout (slow)


def _brownout_push_attempts(budget_enabled: bool) -> int:
    """Run an actor-push brownout (every method push from the driver
    errors ambiguously) and return the number of push ATTEMPTS — the
    chaos rule fires once per push RPC, and every firing leaves a
    chaos.inject event in the cluster log. 8 calls that all fail plus
    their retries = 8 + (retries attempted)."""
    from ray_tpu import chaos
    from ray_tpu._private import event_log
    from ray_tpu.util.state import list_cluster_events

    bk.reset_default_retry_budget()
    CONFIG.set("retry_budget_enabled", budget_enabled)
    CONFIG.set("retry_budget_capacity", 3.0)
    CONFIG.set("retry_budget_fill_per_s", 0.05)
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote(max_restarts=0, max_task_retries=2)
        class Browned:
            def ping(self):
                return True

            def m(self, i):
                return i

        a = Browned.remote()
        # warm a DIFFERENT method: the actor is ALIVE before the chaos
        # plan arms, while `m` stays time-unmeasured so the 8 calls below
        # ride 8 separate push RPCs (one injection each), not one batch
        assert ray_tpu.get(a.ping.remote(), timeout=30)
        plan = chaos.ChaosPlan(seed=7, rules=[
            chaos.ChaosRule(action="error", site="client_request",
                            method="push_task_w", label="driver",
                            maybe_delivered=True),
        ])
        chaos.install(plan)
        try:
            refs = [a.m.remote(i) for i in range(8)]
            failed = 0
            for r in refs:
                try:
                    ray_tpu.get(r, timeout=120)
                except Exception:  # noqa: BLE001 — brownout: all fail
                    failed += 1
            assert failed == 8
        finally:
            chaos.uninstall()
        event_log.flush(timeout=2.0)
        fired = list_cluster_events(etype="chaos.inject", limit=1000)
        return len(fired)
    finally:
        ray_tpu.shutdown()
        bk.reset_default_retry_budget()


@pytest.mark.slow
def test_brownout_retry_amplification_bounded_by_budget():
    """THE anti-retry-storm property: with budgets off, 8 failing calls
    x 2 retries each amplify the brownout into ~24 push attempts; with
    the (peer,method) token bucket at capacity 3 the owner spends at
    most a bucketful of retries before failing fast with the underlying
    error — attempts stay ~8+3."""
    try:
        attempts_off = _brownout_push_attempts(budget_enabled=False)
        attempts_on = _brownout_push_attempts(budget_enabled=True)
    finally:
        CONFIG.set("retry_budget_enabled", True)
        bk.reset_default_retry_budget()
    # unbudgeted: initial 8 + ~16 retries (each spec burns retries_left)
    assert attempts_off >= 20, attempts_off
    # budgeted: initial 8 + ~capacity(3) retries + refill slop
    assert attempts_on <= 14, attempts_on
    assert attempts_on < attempts_off