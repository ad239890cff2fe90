"""The record the train step keeps of every step
(`_private/device_profiler.StepCadence`): intervals and stalls from marks
whose numbers the cases hand in (no case asserts a wall-clock bound), the
stall records' way through `snapshot` / `delta` / `merge`, the benchmark's
readers of them, and one `fit()` on the CPU whose step really sleeps."""

import json
import logging
import os
import threading

import pytest

from ray_tpu._private import device_profiler as dp
from ray_tpu._private.device_profiler import Mark, StepCadence

pytestmark = pytest.mark.profiling

# the cases' clock: far from `perf_counter_ns`, so that no real record of the
# ring (another test's, a background thread's) lies in a scripted interval
T0 = 10**17
MS = 10**6
S = 10**9


def _mark(now, cpu=0, host=None, rusage=None, gc=None, jit=None,
          tracing=False):
    return Mark(T0 + now, cpu, host, rusage, gc, jit, tracing)


class _Steps:
    """A cadence fed one step at a time: `step(start_ms, ...)` is a dispatch
    of 2 ms (or `dispatch_ms`) that began `start_ms` after T0, and the mark
    read when it returned."""

    def __init__(self):
        self.next_mark = None
        self.cadence = StepCadence(read=lambda: self.next_mark)

    def step(self, start_ms, dispatch_ms=2, **fields):
        end = (start_ms + dispatch_ms) * MS
        self.next_mark = _mark(end, **fields)
        self.cadence.mark(T0 + start_ms * MS, T0 + end)

    def run(self, starts_ms, **fields):
        for start in starts_ms:
            self.step(start, **fields)


def _healthy(n, every_ms=400, **fields):
    """A cadence after `n` steps `every_ms` apart: n - 1 intervals kept."""
    steps = _Steps()
    steps.run([i * every_ms for i in range(n)], **fields)
    return steps


@pytest.fixture(autouse=True)
def _an_aggregate_of_its_own(monkeypatch):
    """No span, name or stall of another test: "first counted since" and
    "none seen" mean what they mean in a worker whose session just began."""
    monkeypatch.setattr(dp, "_stalls", [])
    monkeypatch.setattr(dp, "_tables", [])
    monkeypatch.setattr(dp, "_retired", [{}, {}])
    monkeypatch.setattr(dp, "_ring", type(dp._ring)(maxlen=dp.RING_RECORDS))
    monkeypatch.setattr(dp, "_local", dp._Table())


def _since(before):
    return dp.delta(dp.snapshot(), before)


def _ring(name):
    return [r for r in dp.snapshot(recent=dp.RING_RECORDS)["recent"]
            if r["name"] == name and r["start"] > (T0 + dp._EPOCH_NS) * 1e-9]


# ------------------------------------------------------------ the intervals

def test_interval_on_cpu_and_off_cpu_from_two_marks():
    before = dp.snapshot()
    steps = _Steps()
    steps.step(0, cpu=0)
    steps.step(1000, cpu=10 * MS)
    steps.step(1500, dispatch_ms=3, cpu=40 * MS)
    got = _since(before)["spans"]
    # start of the third dispatch less start of the second
    assert got["train.step.interval"] == {
        "count": 1, "total_s": 0.5, "max_s": 0.5, "self_s": 0.5}
    assert got["train.step.off_cpu"]["total_s"] == pytest.approx(0.47)
    (rec,) = _ring("train.step.interval")[-1:]
    assert rec["attrs"] == {"step": 2, "dispatch_s": pytest.approx(0.002),
                            "on_cpu_s": pytest.approx(0.03),
                            "off_cpu_s": pytest.approx(0.47)}
    assert rec["end"] - rec["start"] == pytest.approx(0.5)
    # the aggregate only, as `engine.queue_wait` is
    assert not _ring("train.step.off_cpu")


def test_the_first_interval_is_left_out_and_the_names_are_watched_at_zero():
    before = dp.snapshot()
    steps = _Steps()
    steps.step(0)
    got = _since(before)["spans"]
    # from the first mark: "watched, none seen" reads 0, not "no such span"
    zero = {"count": 0, "total_s": 0.0, "max_s": 0.0, "self_s": 0.0}
    assert got["train.step.stall"] == zero
    assert got["train.step.profiler_toggle"] == zero
    steps.step(30_000)   # the step's trace, lowering and compile
    got = _since(before)["spans"]
    assert "train.step.interval" not in got
    assert "train.step.off_cpu" not in got
    steps.run([30_400, 30_800])
    got = _since(before)
    assert got["spans"]["train.step.interval"]["count"] == 2
    assert got["spans"]["train.step.interval"]["max_s"] == pytest.approx(0.4)
    assert got["stalls"] == []


@pytest.mark.parametrize("median_ms, extra_ms, stalls", [
    (400, 99, False),     # 25% over, under 0.1 s
    (400, 101, True),     # both
    (1000, 150, False),   # over 0.1 s, under 20%
    (1000, 199, False),
    (1000, 201, True),
    (100, 90, False),     # 90% over, under 0.1 s: a short step may swing
    (100, 101, True),
])
def test_a_stall_is_over_the_median_by_a_tenth_of_a_second_and_a_fifth(
        median_ms, extra_ms, stalls):
    before = dp.snapshot()
    steps = _healthy(10, every_ms=median_ms)
    late = 9 * median_ms + median_ms + extra_ms
    steps.step(late)
    got = _since(before)
    assert len(got["stalls"]) == int(stalls)
    stalled = got["spans"]["train.step.stall"]
    assert stalled["count"] == int(stalls)
    if stalls:
        # the lost time: the interval less the running median
        assert stalled["total_s"] == pytest.approx(extra_ms * 1e-3)
        (stall,) = got["stalls"]
        assert stall["step"] == 10
        assert stall["interval_s"] == pytest.approx(
            (median_ms + extra_ms) * 1e-3)
        assert stall["median_s"] == pytest.approx(median_ms * 1e-3)
        assert stall["start"] == pytest.approx(
            (T0 + 9 * median_ms * MS + dp._EPOCH_NS) * 1e-9)
    # a stall is an interval too: the longest step is `max_s`
    assert got["spans"]["train.step.interval"]["max_s"] == pytest.approx(
        (median_ms + extra_ms) * 1e-3)


@pytest.mark.parametrize("steps_before, stalls", [(8, 0), (9, 1)])
def test_no_stall_before_eight_intervals_are_kept(steps_before, stalls):
    before = dp.snapshot()
    steps = _healthy(steps_before)   # steps_before - 1 intervals
    steps.step(steps_before * 400 + 5000)
    assert len(_since(before)["stalls"]) == stalls


def test_the_median_is_of_the_last_thirty_two():
    before = dp.snapshot()
    steps = _Steps()
    steps.run([i * 1000 for i in range(41)])   # 40 intervals: 32 are kept
    # the pace changes for good: the first long steps stall, and from the
    # step that finds half the window at the new pace on none does
    t = 40_000
    for _ in range(40):
        t += 2000
        steps.step(t)
    got = _since(before)
    assert got["spans"]["train.step.stall"]["count"] == 17
    assert max(s["step"] for s in dp.snapshot()["stalls"]) <= 41 + 16
    assert dp.CADENCE_KEPT == 32


def test_a_profiler_toggle_is_no_interval_and_no_stall():
    before = dp.snapshot()
    steps = _healthy(10)
    steps.step(4000 + 3000, tracing=True)    # start_trace took 3 s
    steps.step(7400, tracing=True)           # traced steps are steps
    steps.step(7800, tracing=True)
    steps.step(7800 + 2500, tracing=False)   # stop_trace took 2.1 s
    steps.step(10_700, tracing=False)
    got = _since(before)
    assert got["stalls"] == []
    toggles = got["spans"]["train.step.profiler_toggle"]
    assert toggles["count"] == 2
    assert toggles["total_s"] == pytest.approx(3.4 + 2.5)
    interval = got["spans"]["train.step.interval"]
    assert interval["count"] == 8 + 3 and interval["max_s"] == \
        pytest.approx(0.4)
    assert [r["attrs"]["step"] for r in
            _ring("train.step.profiler_toggle")[-2:]] == [10, 13]


def test_an_unreadable_profiler_state_is_not_known_and_toggles_nothing():
    before = dp.snapshot()
    steps = _healthy(10, tracing=None)
    assert "train.step.profiler_toggle" not in _since(before)["spans"]
    steps.step(4000 + 3000, tracing=None)
    got = _since(before)
    assert "train.step.profiler_toggle" not in got["spans"]
    assert len(got["stalls"]) == 1
    # jax's own state, through `sys.modules`: readable once jax is there
    import jax.profiler  # noqa: F401

    assert dp._profiler_running() is False


# --------------------------------------------------------- the stall record

def test_a_stall_holds_what_the_os_the_collector_and_jax_did_meanwhile():
    before = dp.snapshot()
    hz = round(1 / dp._JIFFY_S)
    steps = _Steps()
    # jiffies (steal, iowait, idle, busy); (nivcsw, majflt); (count, ns)
    for i in range(10):
        steps.step(i * 400, cpu=i * 5 * MS, host=(7, 3, 1000 * i, 100 * i),
                   rusage=(4, 1), gc=(2, S), jit=(50, 20 * S))
    steps.step(3600 + 1700, cpu=45 * MS + 20 * MS,
               host=(7 + hz // 2, 3 + hz // 4, 9000 + 800, 900 + 200),
               rusage=(9, 4), gc=(3, S + 250 * MS), jit=(52, 20 * S + 80 * MS))
    got = _since(before)
    (stall,) = got["stalls"]
    assert stall["pid"] == os.getpid() and stall["step"] == 10
    assert stall["interval_s"] == pytest.approx(1.7)
    assert stall["dispatch_s"] == pytest.approx(0.002)
    assert stall["marks_s"] == pytest.approx(1.7)
    assert stall["on_cpu_s"] == pytest.approx(0.02)
    assert stall["off_cpu_s"] == pytest.approx(1.68)
    assert stall["steal_s"] == pytest.approx(0.5)
    assert stall["iowait_s"] == pytest.approx(0.25)
    jiffies = hz // 2 + hz // 4 + 800 + 200
    assert stall["host_busy_share"] == pytest.approx(100 * 200 / jiffies)
    assert (stall["nivcsw"], stall["majflt"]) == (5, 3)
    assert (stall["gc_count"], stall["compiles"]) == (1, 2)
    assert stall["gc_s"] == pytest.approx(0.25)
    assert stall["jit_s"] == pytest.approx(0.08)
    # no other thread timed anything: silent for all of the interval
    assert stall["others_silent_s"] == pytest.approx(1.7)
    assert stall["overlapping"] == []
    # steal is the record's alone: a span of it would have no reader
    assert "host.steal" not in got["spans"]
    line = dp.stall_line(stall)
    assert "train step 10" in line and "steal 0.500 s" in line
    assert "\n" not in line


def test_a_stall_inside_the_dispatch_reads_from_the_mark_before_it():
    """A recompile (or a freeze) INSIDE a dispatch ends before the mark that
    closes that dispatch is read: the record reaches one mark further back,
    so a step that recompiled says so."""
    steps = _Steps()
    for i in range(10):
        steps.step(i * 400, cpu=i * 5 * MS, jit=(50, 20 * S), gc=(0, 0))
    # the eleventh call traces and compiles for 6 s, on the CPU
    steps.step(4000, dispatch_ms=6000, cpu=50 * MS + 6 * S,
               jit=(51, 26 * S), gc=(0, 0))
    assert dp.snapshot()["stalls"] == []   # its interval is still open
    steps.step(10_400, cpu=55 * MS + 6 * S, jit=(51, 26 * S), gc=(0, 0))
    (stall,) = dp.snapshot()["stalls"]
    assert stall["step"] == 11
    assert stall["interval_s"] == pytest.approx(6.4)
    assert stall["dispatch_s"] == pytest.approx(6.0)
    assert (stall["compiles"], stall["jit_s"]) == (1, pytest.approx(6.0))
    assert stall["on_cpu_s"] == pytest.approx(6.01)
    # from the tenth dispatch's end to the twelfth's
    assert stall["marks_s"] == pytest.approx(10.402 - 3.602)


def test_overlapping_is_other_threads_ring_records_and_open_spans():
    steps = _healthy(10)
    a, b = T0 + 3600 * MS, T0 + 5600 * MS     # the interval to come

    def other():
        dp.record("t.pushed", a + 100 * MS, a + 400 * MS)
        dp.record("t.pushed", a + 900 * MS, b + 5 * S)     # ends after it
        dp.record("t.flushed", a - 5 * S, a + 50 * MS)     # began before it
        dp.record("t.elsewhere", a - 2 * S, a - S)
        for i in range(9):   # more names than are kept
            dp.record(f"t.small{i}", a, a + (i + 1) * MS)

    held, release = threading.Event(), threading.Event()

    def holder():
        with dp.span("t.held"):
            with dp.span("t.held.inner"):
                held.set()
                release.wait(30)

    threads = [threading.Thread(target=other, name="t-cadence-other"),
               threading.Thread(target=holder, name="t-cadence-holder")]
    for t in threads:
        t.start()
    threads[0].join(30)
    assert held.wait(30)
    dp.record("t.mine", a + 100 * MS, a + 1900 * MS)   # this thread's own
    try:
        steps.step(5600)
    finally:
        release.set()
        threads[1].join(30)
    assert not any(t.is_alive() for t in threads)
    (stall,) = dp.snapshot()["stalls"]
    over = stall["overlapping"]
    assert len(over) == 8 and all(o["thread"].startswith("t-cadence-")
                                  for o in over)
    by_name = {o["name"]: o for o in over}
    # a span another thread still has open is no ring record yet
    assert by_name["t.held"] == {
        "name": "t.held", "thread": "t-cadence-holder", "open": True,
        "overlap_s": pytest.approx(2.0), "count": 1}
    assert by_name["t.held.inner"]["open"] is True
    assert by_name["t.pushed"] == {
        "name": "t.pushed", "thread": "t-cadence-other", "count": 2,
        "overlap_s": pytest.approx(0.3 + 1.1)}
    assert by_name["t.flushed"]["overlap_s"] == pytest.approx(0.05)
    assert "t.elsewhere" not in by_name and "t.mine" not in by_name
    # the eight largest, largest first
    assert [o["overlap_s"] for o in over] == sorted(
        (o["overlap_s"] for o in over), reverse=True)
    assert "t.small0" not in by_name and "t.small8" in by_name
    # the other threads last read a clock 0.9 s into the interval's 2 s
    assert stall["others_silent_s"] == pytest.approx(1.1)
    line = dp.stall_line(stall)
    assert "t.held@t-cadence-holder 2.000 s x 1 (open)" in line
    assert "no other thread heard for 1.100 s of it" in line


def test_a_span_that_is_being_entered_has_no_start_and_is_left_out():
    steps = _healthy(10)
    entered, release = threading.Event(), threading.Event()

    def holder():
        # `_Span.__enter__` as far as `top`: the annotation is still being
        # built, `_t0` is not set (`__slots__`: reading it would raise)
        half = dp._Span("t.half_entered", {})
        half._parent = None
        dp._local.top = half
        entered.set()
        release.wait(30)

    thread = threading.Thread(target=holder, name="t-cadence-entering")
    thread.start()
    assert entered.wait(30)
    try:
        steps.step(5600)
    finally:
        release.set()
        thread.join(30)
    (stall,) = dp.snapshot()["stalls"]
    assert stall["interval_s"] == pytest.approx(2.0)
    assert stall["overlapping"] == []


def test_a_fault_in_the_stall_record_is_logged_and_the_step_goes_on(
        monkeypatch, caplog):
    def broken(start_ns, end_ns):
        raise AttributeError("_t0")

    monkeypatch.setattr(dp, "_overlapping", broken)
    before = dp.snapshot()
    steps = _healthy(10)
    with caplog.at_level(logging.ERROR, logger=dp.logger.name):
        steps.step(5600)    # raises nothing into the train step
        steps.step(6000)
    assert "no record of the stall at step 10" in caplog.text
    got = _since(before)
    assert got["stalls"] == []
    # the lost time was counted before the record was built, and the
    # cadence goes on: the long interval and the next are both kept
    assert got["spans"]["train.step.stall"]["total_s"] == pytest.approx(1.6)
    assert got["spans"]["train.step.interval"]["count"] == 10


def test_the_eight_longest_stalls_are_kept():
    steps = _healthy(10)
    t = 3600
    extras = [300, 900, 200, 1000, 500, 700, 150, 800, 600, 400]
    for extra in extras:
        t += 400 + extra
        steps.step(t)
        for _ in range(3):   # healthy steps between
            t += 400
            steps.step(t)
    kept = [round(s["interval_s"] * 1000) - 400
            for s in dp.snapshot()["stalls"]]
    assert kept == sorted(extras, reverse=True)[:8] and dp.STALLS_KEPT == 8


def test_the_worker_logs_a_stall_when_it_happens_at_most_one_in_ten_seconds(
        caplog):
    steps = _healthy(10)
    with caplog.at_level(logging.WARNING, logger=dp.logger.name):
        steps.step(3600 + 1000)    # logged
        steps.step(5000)
        steps.step(5400 + 3000)    # 3.8 s after the first: kept, not logged
        steps.step(8800)
        steps.step(9200 + 7000)    # 11.6 s after it: logged
    lines = [r.getMessage() for r in caplog.records
             if r.name == dp.logger.name]
    assert len(dp.snapshot()["stalls"]) == 3
    assert len(lines) == 2
    assert "train step 10" in lines[0] and "train step 14" in lines[1]


# ------------------------------------------------- missing sources, real ones

def test_a_missing_proc_stat_gives_none_fields_and_nothing_raises(tmp_path):
    assert dp.host_jiffies(str(tmp_path / "no-such-file")) is None
    empty = tmp_path / "stat"
    empty.write_text("")
    assert dp.host_jiffies(str(empty)) is None
    empty.write_text("intr 1 2 3\n")
    assert dp.host_jiffies(str(empty)) is None
    # gVisor's: the file is there and counts nothing
    empty.write_text("cpu  0 0 0 0 0 0 0 0 0 0\ncpu0 0 0 0 0 0 0 0 0 0 0\n")
    assert dp.host_jiffies(str(empty)) is None
    before = dp.snapshot()
    steps = _healthy(10)    # every source but the clocks missing
    steps.step(3600 + 2000)
    got = _since(before)
    (stall,) = got["stalls"]
    for key in ("steal_s", "iowait_s", "host_busy_share", "nivcsw", "majflt",
                "gc_s", "gc_count", "jit_s", "compiles"):
        assert stall[key] is None, key
    assert stall["off_cpu_s"] == pytest.approx(2.0)
    assert "steal ? s" in dp.stall_line(stall)
    json.dumps(got["stalls"])   # plain numbers and short strings


def test_host_jiffies_reads_the_first_line_of_proc_stat(tmp_path):
    stat = tmp_path / "stat"
    stat.write_text("cpu  100 20 30 4000 50 6 7 80 9 10\n"
                    "cpu0 1 2 3 4 5 6 7 8 9 10\n")
    # (steal, iowait, idle, busy = user + nice + system + irq + softirq)
    assert dp.host_jiffies(str(stat)) == (80, 50, 4000, 163)
    here = dp.host_jiffies()
    assert here is None or (min(here) >= 0 and here[2] + here[3] > 0)


def test_a_mark_reads_every_source_that_is_there(monkeypatch):
    dp.install_compile_listener()
    read = dp.MarkReader()
    m, later = read(), read()
    assert later.now >= m.now and later.cpu >= m.cpu
    assert m.gc is not None and len(m.gc) == 2
    assert m.jit is not None and len(m.jit) == 2
    assert m.rusage is not None and len(m.rusage) == 2
    assert m.tracing in (None, False)
    assert (m.host is None) == (dp.host_jiffies() is None)
    # a source that is missing when first asked is not asked again
    asked = []
    monkeypatch.setattr(dp, "host_jiffies", lambda: asked.append(1))
    read = dp.MarkReader()
    assert [read().host, read().host, read().host] == [None] * 3
    assert asked == [1] and read().rusage is not None


def test_a_kernel_that_counts_no_switch_and_no_fault_has_no_rusage(
        monkeypatch):
    import resource

    counts = dict(ru_nvcsw=0, ru_nivcsw=0, ru_minflt=0, ru_majflt=0)
    asked = []

    def getrusage(who):
        asked.append(who)
        return type("usage", (), counts)

    monkeypatch.setattr(resource, "getrusage", getrusage)
    # gVisor's: the call is there and counts nothing; asked once, as a
    # `/proc/stat` of zeros is
    read = dp.MarkReader()
    assert [read().rusage, read().rusage, read().rusage] == [None] * 3
    assert asked == [resource.RUSAGE_SELF]
    # a process that was never preempted and never went to disk still
    # yielded and touched its pages: its zeros are counts
    counts.update(ru_nvcsw=12, ru_minflt=3400)
    assert dp.MarkReader()().rusage == (0, 0)


# ------------------------------------------------- snapshot, delta and merge

def test_delta_and_merge_carry_stalls_and_the_zero_count_name(caplog):
    before = dp.snapshot()
    steps = _healthy(10)
    early = dp.snapshot()
    steps.step(3600 + 1500)
    steps.step(5500)
    steps.step(5900 + 2500)
    sent = dp.delta(dp.snapshot(), before)
    assert [s["step"] for s in sent["stalls"]] == [12, 10]   # longest first
    # ... those that began after the earlier snapshot, and the zero stays
    late = dp.snapshot()
    steps.step(8800)
    steps.step(9200 + 900)
    assert [s["step"] for s in dp.delta(dp.snapshot(), late)["stalls"]] == [
        14]
    assert dp.delta(early, before)["spans"]["train.step.stall"]["count"] == 0
    assert dp.delta(early, before)["stalls"] == []

    # the other process: nothing of this one's is in its aggregate
    dp._stalls.clear()
    caplog.clear()
    healthy = dp.delta(early, before)
    at = dp.snapshot()
    with caplog.at_level(logging.WARNING, logger=dp.logger.name):
        dp.merge(healthy)
        assert dp.snapshot()["stalls"] == [] and not caplog.records
        dp.merge(json.loads(json.dumps(sent)))    # as it comes off a wire
    got = dp.delta(dp.snapshot(), at)
    assert [s["step"] for s in got["stalls"]] == [12, 10]
    assert got["stalls"] == sent["stalls"]
    assert got["spans"]["train.step.stall"]["count"] == 2
    assert got["spans"]["train.step.stall"]["total_s"] == pytest.approx(
        (1.5 - 0.4) + (2.9 - 0.4))
    # ONE warning line a merged stall
    lines = [r.getMessage() for r in caplog.records]
    assert len(lines) == 2 and "train step 12" in lines[0]
    # merged again and again, the eight longest stay
    for _ in range(5):
        dp.merge(sent)
    assert len(dp.snapshot()["stalls"]) == 8


@pytest.mark.parametrize("payload_stalls", [
    "absent", None, [], "a string", [{"step": 3}], [{"interval_s": "long"}]])
def test_a_payload_without_stalls_merges_as_before_and_a_bad_one_not_at_all(
        payload_stalls):
    payload = {"pid": 1, "counters": {"t.cadence.n": 2}, "spans": {
        "t.cadence.merged": {"count": 1, "total_s": 0.5, "max_s": 0.5,
                             "self_s": 0.5}}}
    if payload_stalls != "absent":
        payload["stalls"] = payload_stalls
    before = dp.snapshot()
    dp.merge(payload)
    got = _since(before)
    assert got["stalls"] == []
    good = payload_stalls in ("absent", None, [])
    assert ("t.cadence.merged" in got["spans"]) == good
    assert ("t.cadence.n" in got["counters"]) == good


# ----------------------------------------------------- the benchmark's readers

def test_step_readers_read_a_mean_and_a_share_or_none():
    from benchmarks import span_readers, step_readers

    def spans(total, n):
        return {"count": n, "total_s": total, "max_s": total, "self_s": total}

    readings = {"spans": {"train.step.interval": spans(48.0, 100),
                          "train.step.off_cpu": spans(46.8, 100),
                          "train.step.stall": spans(0.0, 0)}}
    mean = {"span": "train.step.interval", "scale": 1000}
    share = {"part": "train.step.off_cpu", "whole": "train.step.interval"}
    assert step_readers.mean(mean, readings, {}) == pytest.approx(480.0)
    assert step_readers.share_outside(share, readings, {}) == \
        pytest.approx(2.5)
    # "watched, none seen" reads 0.0 through the accepted reader
    assert span_readers.span_seconds(
        {"spans": ["train.step.stall"], "field": "total_s"}, readings,
        {}) == 0.0
    # a commit before the cadence: the metric is left out of the line
    older = {"spans": {"train.step.dispatch": spans(1.0, 100)}}
    assert step_readers.mean(mean, older, {}) is None
    assert step_readers.share_outside(share, older, {}) is None
    assert step_readers.mean({"span": "train.step.stall"}, readings,
                             {}) is None   # counted nothing: no mean
    assert step_readers.share_outside(share, {"spans": {}}, {}) is None


def test_benchmark_json_lists_the_five_metrics_of_the_cadence():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    named = {m["name"]: m for m in bench["per_layer"]}
    readers = {
        "train_step_interval_max_s": "span_readers.span_seconds",
        "train_step_stalled_s": "span_readers.span_seconds",
        "train_profiler_toggle_s": "span_readers.span_seconds",
        "train_step_interval_mean_ms": "step_readers.mean",
        "train_thread_on_cpu_share": "step_readers.share_outside"}
    for name, reader in readers.items():
        entry = named[name]
        assert entry["workloads"] == cells, name
        assert (entry["source"], entry["layer"], entry["moves"],
                entry["better"]) == ("program_span", "train step",
                                     "train_tokens_per_s_per_chip", "lower")
        with open(os.path.join(root, "benchmarks", "metrics",
                               name + ".json")) as f:
            assert json.load(f)["reader"] == reader
    # `host.steal` has no metric: the kernel of the machines that run the
    # benchmark (gVisor's) keeps no such count, so no cell could list it
    assert "host_steal_s" not in named


# ------------------------------------------------------- end to end, on the CPU

def test_fit_brings_home_the_stall_of_a_step_that_slept(
        ray_start_regular, tmp_path, caplog):
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.train.backend import JaxConfig

    def _train_fn(config):  # a closure: shipped by value
        import threading
        import time

        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu import train
        from ray_tpu._private.device_profiler import span

        opt = optax.sgd(0.1)
        state, shardings = train.init_train_state(
            lambda key: {"w": jnp.zeros((4,))}, opt, {"w": (None,)},
            train.get_mesh(), jax.random.PRNGKey(0))
        step = train.make_train_step(
            lambda params, batch: jnp.sum((params["w"] - batch) ** 2),
            opt, shardings)
        held, release = threading.Event(), threading.Event()

        def holder():
            with span("test.held_by_another_thread"):
                held.set()
                release.wait(60)

        other = threading.Thread(target=holder, name="test-holder")
        other.start()
        held.wait(60)
        for i in range(1, 13):
            if i == 10:
                time.sleep(0.6)
            state, m = step(state, jnp.ones((4,)))
            loss = float(m["loss"])
        release.set()
        other.join(60)
        train.report({"loss": loss})

    before = dp.snapshot()
    with caplog.at_level(logging.WARNING, logger=dp.logger.name):
        result = JaxTrainer(
            _train_fn, train_loop_config={},
            jax_config=JaxConfig(platform="cpu",
                                 mesh_config=MeshConfig(fsdp=1)),
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="stall", storage_path=str(tmp_path)),
        ).fit()
    assert result.error is None, result.error
    got = _since(before)
    # (a loaded host may stall a CPU step by itself: the one that slept)
    slept = [s for s in got["stalls"] if s["interval_s"] >= 0.55]
    assert len(slept) == 1, got["stalls"]
    (stall,) = slept
    assert stall["step"] == 9      # the call before the sleep opened it
    assert stall["pid"] != os.getpid()
    assert stall["off_cpu_s"] >= 0.5 > stall["on_cpu_s"]
    assert stall["median_s"] < 0.3
    assert stall["compiles"] == 0 and stall["gc_count"] is not None
    held = [o for o in stall["overlapping"]
            if o["name"] == "test.held_by_another_thread"]
    assert held and held[0]["open"] and held[0]["thread"] == "test-holder"
    assert held[0]["overlap_s"] == pytest.approx(stall["interval_s"])
    spans = got["spans"]
    assert spans["train.step.dispatch"]["count"] == 12
    assert spans["train.step.interval"]["count"] == 10   # the first left out
    assert spans["train.step.interval"]["max_s"] == pytest.approx(
        stall["interval_s"])
    assert spans["train.step.stall"]["count"] == len(got["stalls"])
    assert spans["train.step.off_cpu"]["total_s"] <= \
        spans["train.step.interval"]["total_s"]
    # the process that called `fit()` says which step froze, beside what
    lines = [r.getMessage() for r in caplog.records
             if r.name == dp.logger.name and "train step 9 " in r.getMessage()]
    assert len(lines) == 1
    assert "test.held_by_another_thread@test-holder" in lines[0]
