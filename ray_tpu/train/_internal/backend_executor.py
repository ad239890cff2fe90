"""Driver-side executor for a training run.

Reference: ray python/ray/train/_internal/backend_executor.py:66 —
start (:124) builds the WorkerGroup + runs backend.on_start;
start_training (:436) initializes sessions and launches train_fn on every
worker; the fit loop then pulls one result per worker per round
(`get_next_results` barrier semantics) until all workers finish.
Worker failure surfaces as TrainingWorkerError (backend_executor.py:43) and
the trainer restarts the gang from the latest checkpoint (gang-atomic
recovery — SURVEY §7: a failed host means the whole mesh restarts).
"""

from __future__ import annotations

import logging
import threading
import time
from collections import defaultdict
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu._private.device_profiler import merge, span
from ray_tpu._private.event_watch import EventCursor
from ray_tpu.train._internal.storage import StorageContext
from ray_tpu.train._internal.worker_group import WorkerGroup
from ray_tpu.train.backend import BackendConfig
from ray_tpu.train.checkpoint import Checkpoint

logger = logging.getLogger(__name__)


class TrainingWorkerError(RuntimeError):
    """A training worker died or its train_fn raised.

    `preempted` marks a gang that checkpoint-drained after a
    node.preempt_notice: the trainer reschedules it onto a fresh
    placement group without consuming failure budget."""

    def __init__(self, msg: str, preempted: bool = False):
        super().__init__(msg)
        self.preempted = preempted


class _PreemptWatcher(threading.Thread):
    """Driver-side watcher closing the preemptible-TPU loop: polls the
    cluster event log for `node.preempt_notice` events on nodes hosting
    this gang's workers; on a hit, emits `gang.checkpoint_drain` and
    tells EVERY worker to checkpoint-and-drain at its next report —
    gang-atomic, because a mesh gang missing one host must restart as one
    unit anyway (the fresh placement group excludes the draining node)."""

    def __init__(self, worker_group: WorkerGroup,
                 gang_node_ids: List[str], interval_s: float = 1.0,
                 since: Optional[float] = None):
        super().__init__(daemon=True, name="rt-train-preempt-watch")
        self._wg = worker_group
        self._nodes = set(gang_node_ids)
        self._interval = interval_s
        self._stop = threading.Event()
        # `since` = when gang PLACEMENT began, not when this watcher
        # starts: placement + spawn + init_session can take far longer
        # than the cursor's skew slack, and a notice emitted in that
        # window targets nodes the gang just landed on (earlier notices
        # can't — the scheduler excludes draining nodes from placement)
        self._cursor = EventCursor("node.preempt_notice", since=since)
        self.fired = threading.Event()
        self.notice: Optional[dict] = None

    def run(self) -> None:
        while not self._stop.wait(self._interval):
            for ev in self._cursor.poll(limit=100):
                if ev.get("node_id") in self._nodes:
                    self._fire(ev)
                    return

    def _fire(self, notice: dict) -> None:
        from ray_tpu._private import event_log

        self.notice = notice
        reason = (notice.get("data") or {}).get("reason", "")
        event_log.emit("gang.checkpoint_drain",
                       node_id=notice.get("node_id"),
                       reason=reason, world_size=self._wg.num_workers)
        logger.warning(
            "preempt notice for gang node %s (%s): draining %d workers to "
            "their next checkpoint", str(notice.get("node_id"))[:12],
            reason or "no reason", self._wg.num_workers)
        refs = []
        for w in self._wg.workers:
            try:
                refs.append(w.notify_preempt.remote(reason))
            except Exception:  # noqa: BLE001 — worker already gone
                pass
        if refs:
            try:
                ray_tpu.wait(refs, num_returns=len(refs), timeout=10.0)
            except Exception:  # noqa: BLE001 — best-effort fan-out
                pass
        self.fired.set()

    def stop(self) -> None:
        self._stop.set()


class BackendExecutor:
    def __init__(
        self,
        backend_config: BackendConfig,
        num_workers: int,
        resources_per_worker: Dict[str, float],
        placement_strategy: str = "PACK",
        bundles: Optional[List[Dict[str, float]]] = None,
    ):
        self._backend_config = backend_config
        self._backend = backend_config.backend_cls()
        self._num_workers = num_workers
        self._resources = resources_per_worker
        self._strategy = placement_strategy
        self._bundles = bundles
        self.worker_group: Optional[WorkerGroup] = None
        self._preempt_watcher: Optional[_PreemptWatcher] = None
        self._placement_started_at: Optional[float] = None

    def start(self) -> None:
        self._placement_started_at = time.time()
        self.worker_group = WorkerGroup(
            self._num_workers, self._resources, self._strategy,
            bundles=self._bundles)
        with span("train.gang.place", world=self._num_workers):
            self.worker_group.start()
        try:
            self._backend.on_start(self.worker_group, self._backend_config)
        except Exception:
            self.shutdown()
            raise

    def start_training(
        self,
        train_fn: Callable,
        config: Dict[str, Any],
        storage: StorageContext,
        latest_checkpoint: Optional[Checkpoint] = None,
        experiment_name: str = "",
        trial_id: str = "",
    ) -> None:
        wg = self.worker_group
        assert wg is not None, "start() must run first"
        with span("train.gang.session", world=self._num_workers):
            meta = self._init_sessions(
                wg, storage, latest_checkpoint, experiment_name, trial_id)
        with span("train.gang.launch", world=self._num_workers):
            self._backend.on_training_start(wg, self._backend_config)
            ray_tpu.get([
                w.start_training.remote(train_fn, config)
                for w in wg.workers
            ])
        self._preempt_watcher = _PreemptWatcher(
            wg, [m["node_id"] for m in meta],
            since=self._placement_started_at)
        self._preempt_watcher.start()

    def _init_sessions(self, wg, storage, latest_checkpoint,
                       experiment_name, trial_id) -> List[dict]:
        # node_rank / local_rank derived from gang metadata, like the
        # reference's _create_rank_world_size_mappings.
        meta = wg.group_metadata()
        node_ids = []
        for m in meta:
            if m["node_id"] not in node_ids:
                node_ids.append(m["node_id"])
        local_counter: Dict[str, int] = defaultdict(int)
        init_refs = []
        for rank, (worker, m) in enumerate(zip(wg.workers, meta)):
            local_rank = local_counter[m["node_id"]]
            local_counter[m["node_id"]] += 1
            ctx_kwargs = dict(
                world_size=self._num_workers,
                world_rank=rank,
                local_rank=local_rank,
                local_world_size=sum(
                    1 for mm in meta if mm["node_id"] == m["node_id"]),
                node_rank=node_ids.index(m["node_id"]),
                experiment_name=experiment_name,
                trial_id=trial_id,
                trial_name=trial_id,
                storage_path=storage.storage_path,
                trial_dir=storage.trial_dir,
            )
            init_refs.append(
                worker.init_session.remote(
                    ctx_kwargs, latest_checkpoint,
                    storage.next_checkpoint_index()))
        ray_tpu.get(init_refs)
        return meta

    def get_next_results(self, timeout: float = 3600.0) -> Optional[List[dict]]:
        """One result per worker, or None when training completed everywhere.

        Raises TrainingWorkerError if any worker failed or died.
        """
        wg = self.worker_group
        refs = [w.next_result.remote(timeout) for w in wg.workers]
        try:
            results = ray_tpu.get(refs, timeout=timeout)
        except Exception as e:  # noqa: BLE001 — train_fn / actor-death errors
            preempted = (
                (self._preempt_watcher is not None
                 and self._preempt_watcher.fired.is_set())
                or "GangPreemptedError" in str(e))
            raise TrainingWorkerError(str(e), preempted=preempted) from e
        done = [r is None for r in results]
        if all(done):
            return None
        if any(done):
            raise TrainingWorkerError(
                "some training workers finished while others are still "
                "reporting — train_fn must report the same number of times "
                "on every rank")
        return results

    def pause_reporting(self) -> None:
        for w in self.worker_group.workers:
            w.request_stop.remote()

    def finish(self) -> None:
        """End every worker's session. Each hands back what its process
        timed and counted since the session began; rank 0's is merged into
        this process's aggregate (under the `train.fit` span open here), as
        `backend._round` does with the start-up spans: every rank's would
        multiply each total by the world. Its stall records come with it,
        and `merge` logs one line each: which step froze, and beside what."""
        if self.worker_group is not None:
            try:
                left = ray_tpu.get([
                    w.finish.remote() for w in self.worker_group.workers
                ], timeout=30)
                merge(left[0])
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass

    def shutdown(self) -> None:
        if self._preempt_watcher is not None:
            self._preempt_watcher.stop()
            self._preempt_watcher = None
        if self.worker_group is not None:
            try:
                self._backend.on_shutdown(
                    self.worker_group, self._backend_config)
            except Exception:  # noqa: BLE001
                pass
            self.worker_group.shutdown()
            self.worker_group = None
