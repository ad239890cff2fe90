"""The one way to time a region in a process that owns a chip, or in the
driver that starts it: `span`, `count`, `record`, `snapshot`.

    with span("engine.decode_chunk", steps=16, rows=8) as sp:
        ...                      # the region
    sp.seconds                   # what it took, once it has ended

A span does two things, and has no switch:

  * If jax is ALREADY imported in the process it is a
    `jax.profiler.TraceAnnotation("rt." + name)`: under a running
    `jax.profiler` trace the region lies on its thread's line of the
    xplane's `/host:CPU`, on the same clock as the chip's `XLA Ops`, nested
    as entered. With no trace running that is a no-op of the profiler's.
    This module never imports jax to find out (a driver, and the
    benchmark's parent, stay off jax: the chip belongs to the process they
    start); it looks in `sys.modules`.
  * It adds to a per-process aggregate `{name: count, total_s, max_s,
    self_s}` from `time.perf_counter_ns` (self time is the duration less
    what child spans on the same thread cover) and appends `(name, start,
    end, parent, attrs)` to a ring of the last few thousand records.

No fence, no `block_until_ready`, no histogram, no registry lookup; each
thread adds to a table of its own, so the only lock on the way is the
ring's (a `deque`'s own). A span does not wait for the device: around a
jitted call it times the dispatch, and a region that must cover device work
ends at the host transfer that fetches its result (raylint RTL009 holds the
tree to that).

`snapshot()` is what readers read (the benchmark's `span_readers.py`, after
`ray_tpu.shutdown()`: nothing here is reset by it); `delta` subtracts two
snapshots; `merge` grafts a snapshot taken in another process (rank 0 of a
gang hands its start-up spans back on a return value the driver waits for
anyway, and on `finish()` what it did since its session began: spans,
counters and stall records, never the ring) under the span that is open on
the calling thread.

A step function keeps a record of every step (`StepCadence`, one per
`train.make_train_step`): after each dispatch has returned it hands the
dispatch's two ends to `mark`, which reads (`MarkReader`), where the work
happens and
without ever waiting for the device: `now()`, the calling thread's CPU time
(`time.thread_time_ns`), the first line of `/proc/stat` (the HOST's steal,
iowait, idle and busy jiffies), `getrusage(RUSAGE_SELF)` (involuntary
context switches, major faults), the listeners' running totals of `host.gc`
and `jit.*`, and whether a `jax.profiler` trace is running (jax's own state,
through `sys.modules`). A source that is missing reads None. Two successive
marks give `train.step.interval` (start of one dispatch to the start of the
next, with `step`, `dispatch_s`, `on_cpu_s`, `off_cpu_s` in the ring),
`train.step.off_cpu` (the interval less the thread's CPU: it waited for the
device's result, a lock, the GIL, or the OS); the first
interval holds the step's compile and is left out, one across which a trace
started or stopped goes under `train.step.profiler_toggle` alone. An interval
longer than the running median of the last 32 by more than 0.1 s AND 20% is
a stall: its lost time is `train.step.stall`, and one record of it (what the
OS, the collector and jax did meanwhile, which spans of OTHER threads,
closed or still open, ran beside it, and for how long no other thread was
heard, which tells a process that stood still from a step that waited alone:
the ring's reader) is kept in `snapshot()["stalls"]`, the eight longest, and
logged as one warning line.

Beside the spans, for a repeated device program (`DeviceStepProfiler`: the
engine's decode wave) the per-step phase records behind `ray-tpu profile
--device`, and the process's telemetry:

  install_compile_listener / compile_stats   what jax reports of its own
      jit pipeline through `jax.monitoring`, recorded as the spans
      `jit.trace`, `jit.lower`, `jit.compile`, `jit.cache_load` (self time:
      less the jit events that lie inside), the programs that reached the
      backend counted, with `compile.start` / `compile.end` in the event
      log so that a recompile storm shows in `ray-tpu debug postmortem`;
      and `host.gc`, one record a full collection of the process
  hbm_stats   `device.memory_stats()` per device, exported as the gauges
      ray_tpu_hbm_bytes_{in_use,peak}{device} when it is asked (by
      `report()`, `snapshot_all()`, `engine.stats()`: never from a service
      loop)
  observe_phase   ray_tpu_step_phase_seconds{phase,profiler}, which the
      input pipeline (data/dataset.py) feeds from its consumer
"""

from __future__ import annotations

import bisect
import gc
import logging
import os
import statistics
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional

logger = logging.getLogger(__name__)

PHASES = ("input_wait", "h2d", "compile", "device_execute", "reply")

# -- spans -------------------------------------------------------------------

RING_RECORDS = 8192
TRACE_PREFIX = "rt."
# perf_counter_ns -> ns since the epoch, for the records `snapshot` hands out
_EPOCH_NS = time.time_ns() - time.perf_counter_ns()

_span_lock = threading.Lock()   # the list of per-thread tables, not the path
# (thread, spans, counters, the thread's own attributes of `_local`: its open
# span is `["top"]`) of every live thread that has timed something
_tables: List[tuple] = []
# what threads that have ended left behind: [{spans}, {counters}]
_retired: List[dict] = [{}, {}]
_ring: deque = deque(maxlen=RING_RECORDS)
_TraceAnnotation = None


def _fold(spans: dict, counters: dict, into_spans: dict,
          into_counters: dict) -> None:
    for name, (n, total, longest, own) in list(spans.items()):
        a = into_spans.get(name)
        if a is None:
            into_spans[name] = [n, total, longest, own]
        else:
            a[0] += n
            a[1] += total
            a[2] = max(a[2], longest)
            a[3] += own
    for name, n in list(counters.items()):
        into_counters[name] = into_counters.get(name, 0) + n


class _Table(threading.local):
    """One thread's aggregate: nothing on the path of a span is shared."""

    def __init__(self):
        self.spans: Dict[str, list] = {}   # name -> [count, ns, max, self]
        self.counters: Dict[str, int] = {}
        self.top: Optional[_Span] = None
        # ended jit events, for `_jit_span`: [end ns], [self ns summed up]
        self.jit_ends: List[int] = [0]
        self.jit_owns: List[int] = [0]
        thread = threading.current_thread()
        self.thread = thread.name
        with _span_lock:
            for entry in [e for e in _tables if not e[0].is_alive()]:
                _tables.remove(entry)
                _fold(entry[1], entry[2], *_retired)
            _tables.append((thread, self.spans, self.counters, vars(self)))


_local = _Table()


def _annotation_class():
    """`jax.profiler.TraceAnnotation` once jax is in the process; never
    the import that would put it there."""
    global _TraceAnnotation
    jax = sys.modules.get("jax")
    cls = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
    if cls is not None:
        _TraceAnnotation = cls
    return cls


def _add(spans: Dict[str, list], name: str, ns: int, own: int) -> None:
    a = spans.get(name)
    if a is None:
        spans[name] = [1, ns, ns, own]
    else:
        a[0] += 1
        a[1] += ns
        if ns > a[2]:
            a[2] = ns
        a[3] += own


class _Span:
    __slots__ = ("name", "attrs", "ns", "_t0", "_parent", "_child_ns",
                 "_annotation")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.ns = 0

    @property
    def seconds(self) -> float:
        return self.ns * 1e-9

    @property
    def ends(self) -> tuple:
        """(start, end) on `now()`'s clock, once the span has ended."""
        return self._t0, self._t0 + self.ns

    def __enter__(self):
        table = _local
        self._parent = table.top
        table.top = self
        self._child_ns = 0
        cls = _TraceAnnotation or _annotation_class()
        if cls is None:
            self._annotation = None
        else:
            self._annotation = cls(TRACE_PREFIX + self.name, **self.attrs)
            self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        self.ns = ns = t1 - self._t0
        table, parent, name = _local, self._parent, self.name
        # a generator closed on another thread, or out of order: the
        # region still counts, the other thread's stack is left alone
        if table.top is self:
            table.top = parent
            if parent is not None:
                parent._child_ns += ns
        _add(table.spans, name, ns, max(0, ns - self._child_ns))
        _ring.append((name, self._t0, t1,
                      None if parent is None else parent.name,
                      self.attrs, table.thread))
        return False


def span(name: str, **attrs) -> _Span:
    """Context manager over one region; see the module's docstring."""
    return _Span(name, attrs)


now = time.perf_counter_ns   # the clock `record` takes its two ends from


def record(name: str, start_ns: int, end_ns: int, ring: bool = True,
           **attrs) -> None:
    """A region whose ends were read with `now()` where they happened (a
    request's life in the engine, from its caller's enqueue to its last
    token): aggregate and ring, no annotation (the profiler takes none
    after the fact). `ring=False` for a part of a region that leaves a
    record of its own anyway (the request's queue wait)."""
    table = _local
    ns = max(0, end_ns - start_ns)
    _add(table.spans, name, ns, ns)
    if ring:
        _ring.append((name, start_ns, end_ns, None, attrs, table.thread))


def count(name: str, n: int = 1) -> None:
    counters = _local.counters
    counters[name] = counters.get(name, 0) + n


def snapshot(recent: int = 0) -> Dict[str, Any]:
    """This process's aggregate, over every thread that timed something:
    `spans` {name: {count, total_s, max_s, self_s}}, `counters` {name: n},
    `stalls` (the eight longest stall records, see `StepCadence`) and, with
    `recent`, the ring's newest records oldest first (`start` and `end` in
    seconds since the epoch)."""
    spans: Dict[str, list] = {}
    counters: Dict[str, int] = {}
    with _span_lock:
        _fold(*_retired, spans, counters)
        tables = list(_tables)
        stalls = [dict(s) for s in _stalls]
    for _thread, s, c, _own in tables:
        _fold(s, c, spans, counters)  # copies each table in one C call
    _fold(_listened, {}, spans, counters)
    out: Dict[str, Any] = {
        "pid": os.getpid(),
        "spans": {name: {"count": n, "total_s": total * 1e-9,
                         "max_s": longest * 1e-9, "self_s": own * 1e-9}
                  for name, (n, total, longest, own) in spans.items()},
        "counters": counters,
        "stalls": stalls,
    }
    if recent > 0:
        out["recent"] = [
            {"name": name, "start": (t0 + _EPOCH_NS) * 1e-9,
             "end": (t1 + _EPOCH_NS) * 1e-9, "parent": parent,
             "attrs": attrs, "thread": thread}
            for name, t0, t1, parent, attrs, thread in list(_ring)[-recent:]]
    return out


def delta(after: Dict[str, Any], before: Dict[str, Any]) -> Dict[str, Any]:
    """What happened between two snapshots of ONE process (`max_s` is the
    later snapshot's: a maximum cannot be subtracted; `stalls` those the
    earlier one does not hold)."""
    spans = {}
    for name, a in after["spans"].items():
        b = before["spans"].get(name)
        if b is None:
            spans[name] = dict(a)
        elif a["count"] > b["count"]:
            spans[name] = {"count": a["count"] - b["count"],
                           "total_s": a["total_s"] - b["total_s"],
                           "max_s": a["max_s"],
                           "self_s": a["self_s"] - b["self_s"]}
    # a name first counted between the two stays in at 0 ("counted, and it
    # came to nothing": `flash.steps_unmasked` where every step is masked),
    # so a ratio over it reads 0 and not "no such counter"
    counters = {name: n - before["counters"].get(name, 0)
                for name, n in after["counters"].items()
                if n != before["counters"].get(name)}
    # a stall record is never edited, and one that left the eight longest
    # does not come back: what the earlier snapshot lacks began after it
    seen = before.get("stalls") or ()
    stalls = [s for s in after.get("stalls") or () if s not in seen]
    return {"pid": after["pid"], "spans": spans, "counters": counters,
            "stalls": stalls}


def merge(other: Optional[Dict[str, Any]]) -> None:
    """Graft another process's snapshot (or delta) into this one under the
    same names, as children of the span open on the calling thread: that
    span's self time then leaves out what the other process accounted for
    (the sum of its self times, which is what its spans cover). Its stall
    records join this process's (the eight longest stay), one warning line
    each: the process that waited for a gang says which step froze, and
    beside what. What came over the wire may be None (the other side failed
    to build it) or of another shape: then nothing is merged, and nothing
    raised."""
    try:
        spans = {str(name): [int(a["count"]), int(a["total_s"] * 1e9),
                             int(a["max_s"] * 1e9), int(a["self_s"] * 1e9)]
                 for name, a in (other.get("spans") or {}).items()}
        counters = {str(name): n + 0
                    for name, n in (other.get("counters") or {}).items()}
        stalls = [dict(s, interval_s=s["interval_s"] + 0.0)
                  for s in other.get("stalls") or ()]
        lines = [stall_line(s) for s in stalls]
    except Exception:  # noqa: BLE001 — not a snapshot: all or nothing
        return
    table = _local
    _fold(spans, counters, table.spans, table.counters)
    if table.top is not None:
        table.top._child_ns += sum(a[3] for a in spans.values())
    for stall, line in zip(stalls, lines):
        _keep_stall(stall)
        logger.warning("%s", line)


# -- a step function's cadence, and the stalls it names ----------------------

# An interval is a stall when it is longer than the running median by more
# than BOTH (PERF.md §6, PR 52: a share cell's steps swing by tens of ms with
# the capacity that ran, a frozen process loses 0.25 s or more).
STALL_OVER_S = 0.1
STALL_OVER_SHARE = 0.2
CADENCE_KEPT = 32        # intervals the running median is over
CADENCE_BEFORE_STALLS = 8
STALLS_KEPT = 8          # the longest, a process
_OVERLAPPING_KEPT = 8
_STALL_LOG_EVERY_NS = 10 * 10**9
try:
    _JIFFY_S = 1.0 / os.sysconf("SC_CLK_TCK")
except (AttributeError, ValueError, OSError):
    _JIFFY_S = 0.01
_JIT_NAMES = ("jit.trace", "jit.lower", "jit.compile", "jit.cache_load")

_stalls: List[dict] = []   # under `_span_lock`, longest first


class Mark(NamedTuple):
    """What `StepCadence.mark` reads; every source but the two clocks may
    be missing (None)."""
    now: int                       # `now()`
    cpu: int                       # `time.thread_time_ns()`: this thread's
    host: Optional[tuple]          # jiffies (steal, iowait, idle, busy)
    rusage: Optional[tuple]        # (ru_nivcsw, ru_majflt) of the process
    gc: Optional[tuple]            # (count, ns) of `host.gc`
    jit: Optional[tuple]           # (programs at the backend, jit self ns)
    tracing: Optional[bool]        # a `jax.profiler` trace is running


def host_jiffies(path: str = "/proc/stat") -> Optional[tuple]:
    """(steal, iowait, idle, busy) of all the host's CPUs together, in
    jiffies since boot, from the first line of `/proc/stat`: `cpu  user nice
    system idle iowait irq softirq steal ...`. None where there is no such
    file or line, or where the line is all zeros: a kernel that keeps no
    such count (gVisor's, which every TPU machine of PERF.md's runs has)."""
    try:
        with open(path, "rb") as f:
            fields = f.readline().split()
        if fields[0] != b"cpu":
            return None
        user, nice, system, idle, iowait, irq, softirq, steal = map(
            int, fields[1:9])
    except (OSError, ValueError, IndexError):
        return None
    busy = user + nice + system + irq + softirq
    return (steal, iowait, idle, busy) if idle or busy else None


def _rusage() -> Optional[tuple]:
    """(ru_nivcsw, ru_majflt) of the process; None where there is no
    `getrusage`, or where it has counted no switch and no fault of any kind
    by the time a step has run: a kernel that keeps no such count (gVisor's
    again), as the all-zero `/proc/stat`."""
    try:
        import resource

        usage = resource.getrusage(resource.RUSAGE_SELF)
    except (ImportError, OSError, ValueError):
        return None
    if not (usage.ru_nvcsw or usage.ru_nivcsw or usage.ru_minflt
            or usage.ru_majflt):
        return None
    return usage.ru_nivcsw, usage.ru_majflt


def _profiler_running() -> Optional[bool]:
    """jax's own record of a running trace; None (not known) where jax is
    not in the process or keeps it elsewhere. Never the import."""
    state = getattr(sys.modules.get("jax._src.profiler"),
                    "_profile_state", None)
    try:
        return state.profile_session is not None
    except AttributeError:
        return None


class MarkReader:
    """The sources of a mark, read on the calling thread: two clocks, one
    line of `/proc/stat`, one `getrusage`, a few dict reads; nothing here
    waits for the device or takes a lock. What the OS is asked is the
    mark's cost (PERF.md §6, PR 52: a system call is 5-70 us under gVisor),
    so a source that is missing when first asked is not asked again."""

    def __init__(self):
        self._host: Optional[Callable] = host_jiffies
        self._rusage: Optional[Callable] = _rusage

    def __call__(self) -> Mark:
        host = self._host and self._host()
        if host is None:
            self._host = None
        rusage = self._rusage and self._rusage()
        if rusage is None:
            self._rusage = None
        collections = _listened.get("host.gc")
        jit = None
        if _JIT_NAMES[0] in _listened:   # the listeners are installed
            mine = _local.spans        # a jit's events fire on its caller
            found = [mine[name] for name in _JIT_NAMES if name in mine]
            compiled = mine.get("jit.compile")
            jit = (compiled[0] if compiled else 0, sum(a[3] for a in found))
        return Mark(now(), time.thread_time_ns(), host, rusage,
                    collections and (collections[0], collections[1]), jit,
                    _profiler_running())


def _delta(after: Optional[tuple], before: Optional[tuple], i: int,
           scale: float = 1):
    """Field `i` of a source between two marks; None where either lacks it."""
    if after is None or before is None:
        return None
    return (after[i] - before[i]) * scale


def _overlapping(start_ns: int, end_ns: int) -> tuple:
    """What OTHER threads' spans covered of [start, end): the ring's records
    and the spans still open, by name and thread, the largest first; and the
    longest stretch of it, in ns, in which no other thread started or ended
    a span (the background threads wake every 0.5-1 s: where they were
    silent throughout, the whole process stood still, not the step alone)."""
    me = _local.thread
    found: Dict[tuple, list] = {}   # (name, thread, open) -> [ns, count]
    heard = [start_ns, end_ns]      # when another thread read the clock

    def add(name, thread, t0, t1, still_open):
        ns = min(t1, end_ns) - max(t0, start_ns)
        if ns > 0 and thread != me:
            a = found.setdefault((name, thread, still_open), [0, 0])
            a[0] += ns
            a[1] += 1
            heard.extend(t for t in (t0, t1) if start_ns < t < end_ns)

    for name, t0, t1, _parent, _attrs, thread in list(_ring):
        add(name, thread, t0, t1, False)
    with _span_lock:
        tables = list(_tables)
    for thread, _spans, _counters, own in tables:
        sp = own.get("top")
        while sp is not None:   # the open span and those around it
            # one that is being entered this instant has no start yet
            add(sp.name, thread.name, getattr(sp, "_t0", end_ns), end_ns,
                True)
            sp = sp._parent
    heard.sort()
    silent = max(b - a for a, b in zip(heard, heard[1:]))
    largest = sorted(found.items(), key=lambda kv: -kv[1][0])
    return [dict({"name": name, "thread": thread, "overlap_s": ns * 1e-9,
                  "count": n}, **({"open": True} if still_open else {}))
            for (name, thread, still_open), (ns, n)
            in largest[:_OVERLAPPING_KEPT]], silent


def _keep_stall(stall: dict) -> None:
    with _span_lock:
        _stalls.append(stall)
        _stalls.sort(key=lambda s: -s["interval_s"])
        del _stalls[STALLS_KEPT:]


def stall_line(stall: dict) -> str:
    """One stall record as one line of a log."""
    def s(key, form="%.3f"):
        value = stall.get(key)
        return "?" if value is None else form % value

    beside = ", ".join(
        "%s@%s %.3f s x %d%s" % (o["name"], o["thread"], o["overlap_s"],
                                 o.get("count", 1),
                                 " (open)" if o.get("open") else "")
        for o in stall.get("overlapping") or ()) or "no span of another thread"
    return (
        f"train step {stall.get('step')} of pid {stall.get('pid')} stalled: "
        f"{s('interval_s')} s for a median of {s('median_s')} "
        f"(dispatch {s('dispatch_s')}; over {s('marks_s')} s: on the CPU "
        f"{s('on_cpu_s')}, off it {s('off_cpu_s')}; host steal "
        f"{s('steal_s')} s, iowait {s('iowait_s')} s, busy {s('host_busy_share', '%.0f')}%; "
        f"{s('nivcsw', '%d')} involuntary switches, {s('majflt', '%d')} "
        f"major faults; gc {s('gc_s')} s x {s('gc_count', '%d')}, jit "
        f"{s('jit_s')} s x {s('compiles', '%d')}; no other thread heard for "
        f"{s('others_silent_s')} s of it) beside {beside}")


class StepCadence:
    """The record a step function keeps of every step; see the module's
    docstring. `read` is what a mark reads (a `MarkReader` of its own; a
    test hands in numbers). One per step function, called by the thread
    that steps."""

    def __init__(self, read: Optional[Callable[[], Mark]] = None):
        self._read = read or MarkReader()
        self._last: Optional[tuple] = None    # (start, end, Mark)
        self._before: Optional[Mark] = None   # the mark before that one
        self._step = 0                        # dispatches marked
        self._kept: deque = deque(maxlen=CADENCE_KEPT)
        self._logged_ns: Optional[int] = None

    def mark(self, start_ns: int, end_ns: int) -> None:
        """After a dispatch that began at `start_ns` and returned at
        `end_ns` (`now()`'s clock): read the sources, and account for the
        interval that this dispatch's start closed."""
        m = self._read()
        last, self._last = self._last, (start_ns, end_ns, m)
        self._step += 1
        if last is None:
            # watched from here on: "none seen" reads 0, not "no such span"
            watched = ["train.step.stall"]
            if m.tracing is not None:
                watched.append("train.step.profiler_toggle")
            for name in watched:
                _local.spans.setdefault(name, [0, 0, 0, 0])
            return
        start0, end0, m0 = last
        before, self._before = self._before, m0
        if self._step == 2:
            # the first interval: the step's trace, lowering and compile or
            # load, which `train.step.dispatch`'s `max_s` has. Kept for the
            # median, which one long interval among eight does not move.
            self._kept.append(start_ns - start0)
            return
        step = self._step - 1   # the call whose start opened the interval
        if m.tracing != m0.tracing and None not in (m.tracing, m0.tracing):
            record("train.step.profiler_toggle", start0, start_ns, step=step)
            return
        # The thread's CPU between the two marks, which closed the
        # dispatches at the interval's two ends: the interval's own as far
        # as one dispatch is like the next.
        interval = start_ns - start0
        off_cpu = max(0, interval - (m.cpu - m0.cpu))
        record("train.step.interval", start0, start_ns, step=step,
               dispatch_s=(end0 - start0) * 1e-9,
               on_cpu_s=(m.cpu - m0.cpu) * 1e-9, off_cpu_s=off_cpu * 1e-9)
        record("train.step.off_cpu", 0, off_cpu, ring=False)
        kept = self._kept
        if len(kept) >= CADENCE_BEFORE_STALLS:
            median = statistics.median(kept)
            over = interval - median
            if over > STALL_OVER_S * 1e9 and over > STALL_OVER_SHARE * median:
                # the opening dispatch was itself long (a recompile, a
                # freeze inside it): what happened meanwhile lies before
                # the mark that closed it, so read from the mark before
                long_dispatch = end0 - start0 > STALL_OVER_S * 1e9
                try:
                    self._stalled(step, start0, end0, start_ns, median,
                                  before if long_dispatch and before else m0,
                                  m)
                except Exception:  # noqa: BLE001 — the step goes on
                    logger.exception("no record of the stall at step %d",
                                     step)
        kept.append(interval)

    def _stalled(self, step: int, start0: int, end0: int, start_ns: int,
                 median: float, m0: Mark, m: Mark) -> None:
        """Off the path of a healthy step: the one record of a stall.
        `marks_s` is the time between the two marks that every field but
        the interval's own is over."""
        interval = start_ns - start0
        record("train.step.stall", start_ns - int(interval - median),
               start_ns, step=step)
        on_cpu = m.cpu - m0.cpu
        jiffies = None if None in (m.host, m0.host) else sum(m.host) - sum(
            m0.host)
        overlapping, silent = _overlapping(start0, start_ns)
        stall = {
            "pid": os.getpid(), "step": step,
            "start": (start0 + _EPOCH_NS) * 1e-9,
            "interval_s": interval * 1e-9, "median_s": median * 1e-9,
            "dispatch_s": (end0 - start0) * 1e-9,
            "marks_s": (m.now - m0.now) * 1e-9,
            "on_cpu_s": on_cpu * 1e-9,
            "off_cpu_s": max(0, m.now - m0.now - on_cpu) * 1e-9,
            "steal_s": _delta(m.host, m0.host, 0, _JIFFY_S),
            "iowait_s": _delta(m.host, m0.host, 1, _JIFFY_S),
            "host_busy_share": 100.0 * _delta(m.host, m0.host, 3) / jiffies
            if jiffies else None,
            "nivcsw": _delta(m.rusage, m0.rusage, 0),
            "majflt": _delta(m.rusage, m0.rusage, 1),
            "gc_count": _delta(m.gc, m0.gc, 0),
            "gc_s": _delta(m.gc, m0.gc, 1, 1e-9),
            "compiles": _delta(m.jit, m0.jit, 0),
            "jit_s": _delta(m.jit, m0.jit, 1, 1e-9),
            "others_silent_s": silent * 1e-9, "overlapping": overlapping,
        }
        _keep_stall(stall)
        if self._logged_ns is None or \
                m.now - self._logged_ns >= _STALL_LOG_EVERY_NS:
            self._logged_ns = m.now
            logger.warning("%s", stall_line(stall))


# -- compile telemetry (jax.monitoring backend_compile listener) ------------

_lock = threading.Lock()           # profiler registry
_metrics_lock = threading.Lock()   # lazy metric creation
_phase_hist = None
_hbm_gauges = None
_registry: Dict[str, "DeviceStepProfiler"] = {}

# Device phases span ~100µs (one decode chunk) to minutes (a compile
# storm); reuse the control-plane stage layout which covers that range.
_PHASE_BOUNDARIES = [
    1e-5, 5e-5, 1e-4, 5e-4, 1e-3, 5e-3, 0.01, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 15.0, 60.0, 300.0,
]

_compile_lock = threading.Lock()
_compile_listener_installed = False
_time_span_listener = False   # this jax hands its events' two ends over
_compile_seconds = 0.0
_compile_count = 0
_cache_loads = 0
# jax's names for the stages of a jit (jax/_src/dispatch.py), each fired on
# the calling thread with `fun_name=`. A trace fires once per NESTED trace,
# inner first. `backend_compile_duration` wraps `compile_or_get_cached`: it
# fires for every program that reaches it, on a hit of the persistent cache
# too, and then holds the load, which `cache_retrieval_time_sec` (a
# duration only) has just reported from inside it.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_JIT_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    _COMPILE_EVENT: "jit.compile",
    _CACHE_LOAD_EVENT: "jit.cache_load",
}
_JIT_KEPT = 4096   # ended jit events a thread remembers, to nest later ones


def _jit_span(name: str, start_s: float, end_s: float) -> None:
    """One stage of a jit in the aggregate, its ends in `time.time()` seconds
    (not in the ring: a cell's 2.5k-13k nested traces would push everything
    else out of it). Its self time leaves out the jit events of this thread that lie inside
    it: they ended before it did, so they are recorded already, and the
    ones that began after its start are a suffix of the thread's list. So
    the self times of all `jit.*` add up to the time some jit was open,
    and the span open around them leaves that out of its own. A negative
    or non-finite duration records nothing."""
    if not (end_s >= start_s and end_s - start_s < float("inf")):
        return
    t0 = int(start_s * 1e9) - _EPOCH_NS
    t1 = int(end_s * 1e9) - _EPOCH_NS
    table = _local
    ends, owns = table.jit_ends, table.jit_owns   # owns: running sums
    first = max(1, bisect.bisect_right(ends, t0))
    own = max(0, t1 - t0 - (owns[-1] - owns[first - 1]))
    ends.append(t1)
    owns.append(owns[-1] + own)
    if len(ends) > 2 * _JIT_KEPT:
        del ends[:_JIT_KEPT], owns[:_JIT_KEPT]
    top = table.top
    if top is not None and top._t0 <= t0:   # a child, by time, of the
        top._child_ns += own                # span open around the jit
    _add(table.spans, name, t1 - t0, own)


def _on_event_time_span(event: str, start_time: float, end_time: float,
                        **attrs) -> None:
    """jax.monitoring listener: trace, lowering and backend compile (or
    load) of every jit, as jax timed them."""
    name = _JIT_SPANS.get(event)
    if name is None:
        return
    try:
        _jit_span(name, start_time, end_time)
    except Exception:  # noqa: BLE001 — telemetry must never break compiles
        pass


def _on_event_duration(event: str, duration: float, **attrs) -> None:
    """jax.monitoring listener: count the programs that reach the backend
    (a load from the persistent cache is one too) and their seconds, and
    emit compile.start/compile.end so recompile storms are visible in the
    postmortem timeline; record what has no time span of its own as one
    that ends now. Every other event (`compile_time_saved_sec`, which is
    negative when a load was slower than the compile had been) is ignored.
    May fire on any thread — emit() is non-blocking by contract."""
    name = _JIT_SPANS.get(event)
    if name is None:
        return
    global _compile_seconds, _compile_count, _cache_loads
    try:
        end = time.time()
        duration = float(duration)
        if event == _CACHE_LOAD_EVENT or not _time_span_listener:
            _jit_span(name, end - duration, end)
        if event == _CACHE_LOAD_EVENT:
            with _compile_lock:
                _cache_loads += 1
        if event != _COMPILE_EVENT:
            return
        with _compile_lock:
            _compile_seconds += duration
            _compile_count += 1
        from ray_tpu._private.event_log import emit

        # The listener fires at compile END; compile.start carries the
        # true wall start in its data (t_start) — its envelope time is
        # necessarily the emit instant, one compile later than reality.
        emit("compile.start", source=event, t_start=end - duration)
        emit("compile.end", source=event, duration_s=duration)
    except Exception:  # noqa: BLE001 — telemetry must never break compiles
        pass


# No thread's table, `snapshot` adds it: the names the listeners feed, from
# their installation on (a count of 0 says "listened, saw none": a process
# whose every program missed the cache loaded for 0 s), and `host.gc`.
_listened: Dict[str, list] = {}
_gc_t0 = 0


def _on_gc(phase: str, info: dict) -> None:
    """`gc.callbacks` entry: each full collection (generation 2: every
    container the process holds is walked, a model's pytrees among them) as
    a `host.gc` record. The younger generations cost microseconds and run
    all the time: nothing is read or written for them but `info`."""
    global _gc_t0
    try:
        if info.get("generation") != 2:
            return
        if phase == "start":
            _gc_t0 = now()
        elif _gc_t0:
            t0, t1, _gc_t0 = _gc_t0, now(), 0
            # not through `_local`: a collection can start under
            # `_span_lock`, which a thread's first table takes
            # and not into the ring: a collection comes when it will, in
            # the middle of whatever sequence a reader of the ring expects
            _add(_listened, "host.gc", t1 - t0, t1 - t0)
    except Exception:  # noqa: BLE001 — telemetry must never break the host
        pass


def install_compile_listener() -> None:
    """Install the listeners of jax's jit pipeline and of the process's
    full collections (idempotent, process-wide). jax.monitoring has no
    deregistration, so this is once-per-process by design; profilers
    install it on construction, a gang worker before `train_fn` starts."""
    global _compile_listener_installed, _time_span_listener
    with _compile_lock:
        if _compile_listener_installed:
            return
        _compile_listener_installed = True
    heard = ["host.gc"]
    gc.callbacks.append(_on_gc)
    try:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration)
        register = getattr(
            jax.monitoring, "register_event_time_span_listener", None)
        if register is not None:
            register(_on_event_time_span)
            _time_span_listener = True
        heard += _JIT_SPANS.values()
    except Exception:  # noqa: BLE001 — profiling degrades without jax
        pass
    for name in heard:
        _listened.setdefault(name, [0, 0, 0, 0])


def compile_stats() -> Dict[str, float]:
    """Cumulative telemetry of this process's backend: `compiles` the
    programs that reached `compile_or_get_cached` and `compile_s` their
    seconds there, loads from the persistent cache among them
    (`cache_loads` of them; the rest were compiled)."""
    with _compile_lock:
        return {"compiles": _compile_count, "compile_s": _compile_seconds,
                "cache_loads": _cache_loads}


# -- HBM telemetry ----------------------------------------------------------

def _gauges():
    """Lazy per-process gauges (importing this module must not register
    metrics in processes that never ask)."""
    global _hbm_gauges
    with _metrics_lock:
        if _hbm_gauges is None:
            from ray_tpu.util.metrics import Gauge, get_metric

            def gauge(name, desc):
                m = get_metric(name)
                return m if m is not None else Gauge(
                    name, desc, tag_keys=("device",))

            _hbm_gauges = (
                gauge("ray_tpu_hbm_bytes_in_use",
                      "Device memory in use (device.memory_stats)"),
                gauge("ray_tpu_hbm_bytes_peak",
                      "Peak device memory in use (device.memory_stats)"),
            )
        return _hbm_gauges


def hbm_stats(devices: Optional[List[Any]] = None,
              export: bool = True) -> Dict[str, Dict[str, int]]:
    """Per-device HBM occupancy from ``device.memory_stats()``, exported
    as ray_tpu_hbm_bytes_{in_use,peak} gauges. CPU devices (and any PJRT
    backend without memory stats) return None / raise — those devices are
    reported with an empty dict rather than dropped, so the caller can
    tell "no telemetry" from "no device"."""
    if devices is None:
        try:
            import jax

            devices = jax.local_devices()
        except Exception:  # noqa: BLE001 — no backend reachable
            return {}
    out: Dict[str, Dict[str, int]] = {}
    gauges = _gauges() if export else None
    for d in devices:
        label = f"{getattr(d, 'platform', '?')}:{getattr(d, 'id', '?')}"
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — backend without the API
            stats = None
        if not stats:
            out[label] = {}
            continue
        entry = {}
        in_use = stats.get("bytes_in_use")
        peak = stats.get("peak_bytes_in_use", stats.get("bytes_in_use"))
        if in_use is not None:
            entry["bytes_in_use"] = int(in_use)
            if gauges:
                gauges[0].set(float(in_use), tags={"device": label})
        if peak is not None:
            entry["peak_bytes_in_use"] = int(peak)
            if gauges:
                gauges[1].set(float(peak), tags={"device": label})
        if "bytes_limit" in stats:
            entry["bytes_limit"] = int(stats["bytes_limit"])
        out[label] = entry
    return out


def observe_phase(phase: str, seconds: float, profiler: str = "data") -> None:
    """Record one phase sample into the cluster-wide histogram
    ray_tpu_step_phase_seconds — how the input pipeline (data/dataset.py)
    contributes input_wait from its consumer."""
    global _phase_hist
    with _metrics_lock:
        if _phase_hist is None:
            from ray_tpu.util.metrics import get_or_create_histogram

            _phase_hist = get_or_create_histogram(
                "ray_tpu_step_phase_seconds",
                "Per-phase device-step latency (the input pipeline's "
                "input_wait)",
                boundaries=_PHASE_BOUNDARIES,
                tag_keys=("phase", "profiler"),
            )
    _phase_hist.observe(max(0.0, seconds),
                        tags={"phase": phase, "profiler": profiler})


# -- per-step phase records --------------------------------------------------

class DeviceStepProfiler:
    """Phase records of a repeated device program (the engine's decode
    wave), one per step, from durations the caller read off its spans.
    Thread-safe; one instance per logical step stream. Keeps totals and
    the recent steps for `report()`; exports nothing per step."""

    def __init__(self, name: str, *, max_steps: int = 1024):
        self.name = name
        self._steps: deque = deque(maxlen=max_steps)
        self._totals: Dict[str, float] = {}
        self._n = 0
        self._lock = threading.Lock()
        # compile attribution: compiles since this mark belong to the
        # next recorded step
        with _compile_lock:
            self._compile_mark = _compile_seconds
        install_compile_listener()

    def record_step(self, phases: Dict[str, float],
                    tokens: Optional[int] = None,
                    wall0: Optional[float] = None) -> None:
        """Record one step whose phases the caller timed (`span(...)`'s
        `.seconds`; a device phase ends at the host transfer that fetched
        its result). Compile seconds since the previous record fired
        inside one of those phases (almost always device_execute's first
        call): they are carved out of it into a `compile` phase, so that
        the steady-state phase does not wear the compile storm."""
        phases = {k: max(0.0, v) for k, v in phases.items()}
        total = sum(phases.values())
        now_c = _compile_seconds   # one read of a float: no lock needed
        rec = {"time": wall0 if wall0 is not None else time.time() - total,
               "total": total, "phases": phases, "tokens": tokens}
        with self._lock:
            compile_d = now_c - self._compile_mark
            self._compile_mark = now_c
            if compile_d > 0:
                for carve in ("device_execute", "h2d"):
                    if phases.get(carve, 0.0) > 0:
                        phases[carve] = max(0.0, phases[carve] - compile_d)
                        break
                phases["compile"] = phases.get("compile", 0.0) + compile_d
            self._steps.append(rec)
            self._n += 1
            for ph, dur in phases.items():
                self._totals[ph] = self._totals.get(ph, 0.0) + dur

    def report(self, recent: int = 64, emit_event: bool = True,
               include_hbm: bool = True) -> Dict[str, Any]:
        """Aggregate phase report: totals, fractions of accounted time
        (input_wait_frac / device_frac / ...), compile seconds, HBM
        occupancy (which sets the HBM gauges), and the recent per-step
        records `ray-tpu profile --device` renders into chrome-trace
        lanes. recent=0 means NO per-step records; include_hbm=False
        skips the device sweep (snapshot_all does ONE sweep for all
        profilers)."""
        with self._lock:
            totals = dict(self._totals)
            steps = self._n
            recent_steps = list(self._steps)[-recent:] if recent > 0 else []
        accounted = sum(totals.values()) or 1.0
        fracs = {f"{ph}_frac": round(totals.get(ph, 0.0) / accounted, 4)
                 for ph in PHASES}
        for ph in set(totals) - set(PHASES):
            fracs[f"{ph}_frac"] = round(totals[ph] / accounted, 4)
        rep = {
            "profiler": self.name,
            "steps": steps,
            "phase_seconds": {k: round(v, 6) for k, v in totals.items()},
            "accounted_s": round(accounted if totals else 0.0, 6),
            "compile_s": round(totals.get("compile", 0.0), 6),
            **fracs,
            "compile_process": compile_stats(),
            "hbm": hbm_stats() if include_hbm else {},
            "recent_steps": recent_steps,
        }
        if emit_event and steps:
            try:
                from ray_tpu._private.event_log import emit

                emit("perf.phase_report", profiler=self.name, steps=steps,
                     fracs={k: v for k, v in fracs.items()})
            except Exception:  # noqa: BLE001 — reporting is best-effort
                pass
        return rep

    def reset(self) -> None:
        with self._lock:
            self._steps.clear()
            self._totals.clear()
            self._n = 0


def get_profiler(name: str, **kwargs) -> DeviceStepProfiler:
    """Process-wide registry: the engine/train loop creates, the
    profile_device RPC snapshots. Construction kwargs only apply on first
    creation."""
    with _lock:
        prof = _registry.get(name)
        if prof is None:
            prof = _registry[name] = DeviceStepProfiler(name, **kwargs)
        return prof


def snapshot_all(recent: int = 64) -> Dict[str, Any]:
    """Every registered profiler's report and this process's spans — the
    profile_device RPC body."""
    with _lock:
        profs = list(_registry.values())
    spans = snapshot()
    return {
        "pid": os.getpid(),
        "compile": compile_stats(),
        # ONE device sweep for the whole snapshot (per-profiler reports
        # skip theirs — identical data K+1 times otherwise)
        "hbm": hbm_stats(),
        "spans": spans["spans"],
        "counters": spans["counters"],
        "profilers": {p.name: p.report(recent=recent, emit_event=False,
                                       include_hbm=False)
                      for p in profs},
    }


def steps_to_spans(report: Dict[str, Any], proc: str) -> List[Dict[str, Any]]:
    """Render one profiler report's recent steps into span dicts (the
    tracing-module shape) — phases laid back-to-back inside each step, one
    lane per (proc, profiler) — mergeable with PR 1 task-stage spans via
    tracing.trace_chrome."""
    spans: List[Dict[str, Any]] = []
    name = report.get("profiler", "?")
    for i, rec in enumerate(report.get("recent_steps", ())):
        t0 = rec.get("time", 0.0)
        t = t0
        spans.append({
            "span_id": f"dev-{name}-{i}", "parent_id": None,
            "trace_id": None, "name": f"{name}.step",
            "proc": proc, "thread": f"device:{name}",
            "start": t0, "end": t0 + rec.get("total", 0.0),
            "attrs": {"tokens": rec.get("tokens")},
        })
        phases = rec.get("phases", {})
        # canonical phases first for stable ordering, then any custom
        # ones (e.g. the engine's "prefill") — dropping them would show
        # unexplained gaps in an admission-bound engine's lanes
        ordered = [p for p in PHASES if p in phases] + sorted(
            p for p in phases if p not in PHASES)
        for ph in ordered:
            dur = phases.get(ph, 0.0)
            if dur <= 0:
                continue
            spans.append({
                "span_id": f"dev-{name}-{i}-{ph}",
                "parent_id": f"dev-{name}-{i}", "trace_id": None,
                "name": f"{name}:{ph}", "proc": proc,
                "thread": f"device:{name}",
                "start": t, "end": t + dur,
                "attrs": {"phase": ph},
            })
            t += dur
    return spans
