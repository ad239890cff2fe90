"""WorkerGroup: a gang of training-worker actors on a placement group.

Reference: ray python/ray/train/_internal/worker_group.py:102 (start :193,
execute_async :233). Workers are plain actors scheduled into one placement
group so the gang is atomic: either the whole slice is reserved or nothing
runs (SURVEY §7 "SPMD-vs-actor impedance" — a TPU mesh gang must be
scheduled and failed as one unit).
"""

from __future__ import annotations

import logging
import os
import socket
import threading
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu._private import device_profiler
from ray_tpu.util.placement_group import placement_group, remove_placement_group
from ray_tpu.util.scheduling_strategies import PlacementGroupSchedulingStrategy

logger = logging.getLogger(__name__)


class TrainWorker:
    """Actor body hosting the training session (one per gang slot)."""

    def __init__(self):
        self._train_thread: Optional[threading.Thread] = None
        self._session = None
        self._spans_at_session: Optional[dict] = None

    def get_metadata(self) -> Dict[str, Any]:
        ctx = ray_tpu.get_runtime_context()
        return {
            "node_id": ctx.get_node_id(),
            "hostname": socket.gethostname(),
            "pid": os.getpid(),
        }

    def init_session(self, context_kwargs: Dict[str, Any],
                     latest_checkpoint=None,
                     checkpoint_index_start: int = 0) -> None:
        from ray_tpu.train._internal import session as session_mod
        from ray_tpu.train.context import TrainContext

        self._session = session_mod.init_session(
            TrainContext(**context_kwargs), latest_checkpoint,
            checkpoint_index_start)
        # what `finish()` hands the driver is what happened since: the
        # start-up spans before this went back on their rounds' results
        self._spans_at_session = device_profiler.snapshot()

    def run_backend_hook(self, hook: Callable, *args, **kwargs) -> Any:
        return hook(*args, **kwargs)

    def start_training(self, train_fn: Callable, config: Dict[str, Any]) -> None:
        assert self._session is not None, "init_session must run first"
        s = self._session

        def _run():
            try:
                import inspect

                if len(inspect.signature(train_fn).parameters) == 0:
                    train_fn()
                else:
                    train_fn(config)
            except BaseException as e:  # noqa: BLE001 — report any failure
                s.error = e
            finally:
                s.finished.set()

        self._train_thread = threading.Thread(
            target=_run, name="rt-train-fn", daemon=True)
        self._train_thread.start()

    def next_result(self, timeout: float = 3600.0):
        """One report from the train thread, or None when training finished.

        Raises the train thread's error, if any, after it finishes.
        """
        import queue as _q

        s = self._session
        deadline = timeout
        while True:
            try:
                r = s.result_queue.get(timeout=min(0.1, deadline))
                return {"metrics": r.metrics,
                        "checkpoint_dir_name": r.checkpoint_dir_name}
            except _q.Empty:
                deadline -= 0.1
                if s.finished.is_set() and s.result_queue.empty():
                    if s.error is not None:
                        raise s.error
                    return None
                if deadline <= 0:
                    raise TimeoutError("no training result within timeout")

    def request_stop(self) -> None:
        if self._session is not None:
            self._session.stop_requested.set()

    def notify_preempt(self, reason: str = "") -> bool:
        """Advance notice of node loss (driver preempt watcher fan-out):
        arm checkpoint-and-drain so the next checkpointed report unwinds
        the train_fn gang-atomically (see session.GangPreemptedError)."""
        if self._session is None:
            return False
        self._session.request_preempt(reason)
        return True

    def finish(self, timeout: float = 30.0) -> Optional[dict]:
        """End the session; returns what this process timed and counted
        since `init_session` (`device_profiler.delta`: `spans`, `counters`
        and the `stalls` its step functions recorded, plain dicts of numbers
        and short strings, no ring), or None where that could not be had."""
        if self._train_thread is not None:
            self._train_thread.join(timeout)
        from ray_tpu.train._internal import session as session_mod

        session_mod.shutdown_session()
        try:
            return device_profiler.delta(
                device_profiler.snapshot(), self._spans_at_session)
        except Exception:  # noqa: BLE001 — the record is no part of the run
            return None

    def execute(self, fn: Callable, *args, **kwargs) -> Any:
        return fn(*args, **kwargs)


class WorkerGroup:
    """Owns the placement group + actor gang."""

    def __init__(self, num_workers: int,
                 resources_per_worker: Dict[str, float],
                 placement_strategy: str = "PACK",
                 actor_cls=None,
                 bundles: Optional[List[Dict[str, float]]] = None):
        """`bundles` overrides the uniform per-worker resources with one
        dict per worker — TPU topology gangs put the slice's head gang
        resource on bundle 0 only (ScalingConfig.worker_bundles)."""
        self.num_workers = num_workers
        if bundles is not None and len(bundles) != num_workers:
            raise ValueError(
                f"bundles has {len(bundles)} entries for {num_workers} "
                "workers")
        self._bundles = (list(bundles) if bundles is not None
                         else [dict(resources_per_worker)
                               for _ in range(num_workers)])
        self._strategy = placement_strategy
        self._actor_cls = actor_cls or TrainWorker
        self.workers: List[Any] = []
        self._pg = None

    @property
    def demands_tpu(self) -> bool:
        return any(b.get("TPU", 0) > 0 for b in self._bundles)

    def start(self) -> None:
        bundles = [dict(b) for b in self._bundles]
        self._pg = placement_group(bundles, strategy=self._strategy)
        ray_tpu.get(self._pg.ready())
        remote_cls = ray_tpu.remote(self._actor_cls)
        self.workers = [
            remote_cls.options(
                num_cpus=self._bundles[i].get("CPU", 1.0),
                resources={k: v for k, v in self._bundles[i].items()
                           if k != "CPU" and v > 0},
                max_concurrency=4,  # next_result must overlap start_training
                scheduling_strategy=PlacementGroupSchedulingStrategy(
                    placement_group=self._pg,
                    placement_group_bundle_index=i,
                ),
            ).remote()
            for i in range(self.num_workers)
        ]
        # Surface actor-start failures eagerly.
        ray_tpu.get([w.get_metadata.remote() for w in self.workers])

    def execute(self, fn: Callable, *args, **kwargs) -> List[Any]:
        return ray_tpu.get(self.execute_async(fn, *args, **kwargs))

    def execute_async(self, fn: Callable, *args, **kwargs) -> List[Any]:
        return [w.execute.remote(fn, *args, **kwargs) for w in self.workers]

    def execute_single(self, rank: int, fn: Callable, *args, **kwargs) -> Any:
        return ray_tpu.get(self.workers[rank].execute.remote(fn, *args, **kwargs))

    def group_metadata(self) -> List[Dict[str, Any]]:
        return ray_tpu.get([w.get_metadata.remote() for w in self.workers])

    def shutdown(self) -> None:
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
        self.workers = []
        if self._pg is not None:
            remove_placement_group(self._pg)
            self._pg = None
