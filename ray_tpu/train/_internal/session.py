"""Worker-side training session: report / get_checkpoint / get_context.

Reference: ray python/ray/train/_internal/session.py — report (:666 public,
:402 _report), get_checkpoint (:753), get_context (context.py:80).

The session runs the user's train_fn on a separate thread inside the worker
actor. `report(metrics, checkpoint)` persists the checkpoint into run storage
(shared filesystem) and enqueues the result; the driver's BackendExecutor
pulls one result per worker per round (a soft barrier, like the reference's
`get_next_results`). A report from the train thread blocks until the driver
consumes it, which backpressures fast workers to the reporting cadence.
"""

from __future__ import annotations

import os
import queue
import threading
from typing import Any, Dict, Optional

from ray_tpu._private.device_profiler import span
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.context import TrainContext


class GangPreemptedError(RuntimeError):
    """This worker's node got a preemption notice (node.preempt_notice)
    and the train_fn unwound AFTER persisting its drain checkpoint — the
    trainer catches the resulting gang failure and reschedules the whole
    gang onto a fresh placement group without burning failure budget."""


class _TrainingResult:
    __slots__ = ("metrics", "checkpoint_dir_name")

    def __init__(self, metrics, checkpoint_dir_name=None):
        self.metrics = metrics
        self.checkpoint_dir_name = checkpoint_dir_name


class _Session:
    def __init__(self, context: TrainContext,
                 latest_checkpoint: Optional[Checkpoint] = None,
                 checkpoint_index_start: int = 0):
        self.context = context
        self.latest_checkpoint = latest_checkpoint
        self.result_queue: "queue.Queue[_TrainingResult]" = queue.Queue(maxsize=1)
        self.finished = threading.Event()
        self.error: Optional[BaseException] = None
        self.stop_requested = threading.Event()
        self.preempt_requested = threading.Event()
        self.preempt_reason = ""
        self._report_count = checkpoint_index_start

    def request_preempt(self, reason: str = "") -> None:
        """Arm checkpoint-and-drain: the next report() that carries a
        checkpoint persists it and unwinds the train_fn with
        GangPreemptedError (called by TrainWorker.notify_preempt from the
        driver's preempt watcher)."""
        self.preempt_reason = reason
        self.preempt_requested.set()

    # called from the train thread
    def report(self, metrics: Dict[str, Any],
               checkpoint: Optional[Checkpoint] = None) -> None:
        ckpt_name = None
        if checkpoint is not None:
            ckpt_name = self._persist_checkpoint(checkpoint)
            self.latest_checkpoint = checkpoint
        if self.preempt_requested.is_set() and checkpoint is not None:
            # drain ordering contract (tested): the checkpoint above is
            # already persisted to trial storage BEFORE the unwind, so the
            # rescheduled gang resumes from this exact step. Raised before
            # the queue put — the driver is about to tear the gang down
            # and may never consume another result (maxsize=1 would wedge
            # this thread forever).
            raise GangPreemptedError(
                f"node preempted ({self.preempt_reason or 'notice'}); "
                f"drain checkpoint {ckpt_name!r} persisted")
        self._report_count += 1
        self.result_queue.put(_TrainingResult(dict(metrics), ckpt_name))
        if self.stop_requested.is_set():
            raise SystemExit("training stopped by driver")

    def _persist_checkpoint(self, checkpoint: Checkpoint) -> Optional[str]:
        """Copy the worker-local checkpoint dir into trial storage.

        Rank 0 uploads by convention (matching the reference's
        `checkpoint_upload_from_workers=False` default); other ranks report
        metrics only unless they pass a distinct shard directory, in which
        case the shard is stored under the same checkpoint name (multi-host
        sharded checkpoints, each host uploading its own shard).
        """
        trial_dir = self.context.trial_dir
        if trial_dir is None:
            return None
        name = f"checkpoint_{self._report_count:06d}"
        dest = os.path.join(trial_dir, name)
        if self.context.world_rank == 0:
            checkpoint.to_directory(dest)
        elif checkpoint.get_metadata().get("sharded"):
            shard = os.path.join(
                dest, f"shard_{self.context.world_rank:05d}")
            os.makedirs(dest, exist_ok=True)
            checkpoint.to_directory(shard)
        # non-sharded non-zero ranks must not even create the directory:
        # report-count skew between ranks (the queue allows one report in
        # flight) would otherwise leave an EMPTY checkpoint_NNNNNN ahead
        # of rank 0's real one, and a gang restart would "resume" from a
        # payload-less checkpoint (found by the preemption drill)
        return name

    def get_checkpoint(self) -> Optional[Checkpoint]:
        return self.latest_checkpoint


_session_lock = threading.Lock()
_session: Optional[_Session] = None


def init_session(context: TrainContext,
                 latest_checkpoint: Optional[Checkpoint] = None,
                 checkpoint_index_start: int = 0) -> _Session:
    global _session
    with _session_lock:
        _session = _Session(context, latest_checkpoint,
                            checkpoint_index_start)
        return _session


def get_session() -> Optional[_Session]:
    return _session


def shutdown_session() -> None:
    global _session
    with _session_lock:
        _session = None


# -- public API (ray_tpu.train.report / get_checkpoint / get_context) -------

def report(metrics: Dict[str, Any],
           checkpoint: Optional[Checkpoint] = None) -> None:
    s = get_session()
    if s is None:
        raise RuntimeError(
            "ray_tpu.train.report() called outside a training session")
    # the checkpoint's copy to trial storage and the wait for the driver
    # to take the previous result both fall in here
    with span("train.report", checkpoint=checkpoint is not None):
        s.report(metrics, checkpoint)


def get_checkpoint() -> Optional[Checkpoint]:
    s = get_session()
    if s is None:
        raise RuntimeError(
            "ray_tpu.train.get_checkpoint() called outside a training session")
    return s.get_checkpoint()


def get_context() -> TrainContext:
    s = get_session()
    if s is None:
        return TrainContext()
    return s.context
