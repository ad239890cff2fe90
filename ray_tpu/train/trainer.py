"""BaseTrainer / DataParallelTrainer / JaxTrainer.

Reference: ray python/ray/train/base_trainer.py:567 (fit),
data_parallel_trainer.py:428 (training_loop over BackendExecutor).
The reference wraps every fit in a single-trial Tune run (base_trainer.py:
607-623); here fit() drives the executor directly and ray_tpu.tune reuses
this trainer as a trainable — same composition, inverted, which avoids a
hard tune dependency in train.

Fault tolerance matches the reference's FailureConfig semantics: on a
TrainingWorkerError the gang is torn down and restarted from the latest
persisted checkpoint, up to max_failures times.
"""

from __future__ import annotations

import logging
import time
import uuid
from typing import Any, Callable, Dict, Optional

from ray_tpu._private.device_profiler import span
from ray_tpu.air import Result, RunConfig, ScalingConfig
from ray_tpu.train._internal.backend_executor import (
    BackendExecutor,
    TrainingWorkerError,
)
from ray_tpu.train._internal.storage import StorageContext
from ray_tpu.train.backend import BackendConfig, JaxConfig
from ray_tpu.train.checkpoint import Checkpoint

logger = logging.getLogger(__name__)


class BaseTrainer:
    def __init__(
        self,
        *,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        resume_from_checkpoint: Optional[Checkpoint] = None,
        metadata: Optional[Dict[str, Any]] = None,
    ):
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.resume_from_checkpoint = resume_from_checkpoint
        self.metadata = metadata or {}

    def fit(self) -> Result:
        raise NotImplementedError

    def as_trainable(self) -> Callable:
        """A tune-compatible function trainable wrapping this trainer."""
        trainer = self

        def _trainable(config: Dict[str, Any]):
            import copy

            t = copy.copy(trainer)
            if hasattr(t, "train_loop_config"):
                merged = dict(t.train_loop_config or {})
                merged.update(config)
                t.train_loop_config = merged
            t.fit()

        _trainable.__name__ = type(self).__name__
        return _trainable


class DataParallelTrainer(BaseTrainer):
    """Runs train_loop_per_worker as an SPMD gang of actor workers."""

    _default_backend_config: BackendConfig = BackendConfig()

    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        backend_config: Optional[BackendConfig] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
        resume_from_checkpoint: Optional[Checkpoint] = None,
        metadata: Optional[Dict[str, Any]] = None,
    ):
        super().__init__(
            scaling_config=scaling_config,
            run_config=run_config,
            resume_from_checkpoint=resume_from_checkpoint,
            metadata=metadata,
        )
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config or {}
        self.backend_config = backend_config or self._default_backend_config
        self.datasets = datasets or {}

    # -- fit ----------------------------------------------------------------

    def fit(self) -> Result:
        name = self.run_config.name or f"train_{int(time.time())}"
        trial_id = uuid.uuid4().hex[:8]
        storage = StorageContext(self.run_config.storage_path, name, trial_id)
        max_failures = self.run_config.failure_config.max_failures
        latest_checkpoint = self.resume_from_checkpoint
        attempts = 0
        preemptions = 0
        while True:
            try:
                with span("train.fit", attempt=attempts + preemptions):
                    return self._run_attempt(storage, latest_checkpoint,
                                             name, trial_id)
            except TrainingWorkerError as e:
                if getattr(e, "preempted", False):
                    # announced node loss: the gang checkpoint-drained on
                    # notice, so this is a reschedule, not a failure — it
                    # never burns failure budget (bounded only by a large
                    # runaway backstop)
                    preemptions += 1
                    if preemptions > 64:
                        raise
                else:
                    attempts += 1
                if max_failures != -1 and attempts > max_failures:
                    last = storage.latest_checkpoint()
                    return Result(
                        metrics=None,
                        checkpoint=Checkpoint(last) if last else None,
                        path=storage.trial_dir,
                        error=e,
                    )
                last = storage.latest_checkpoint()
                latest_checkpoint = Checkpoint(last) if last else None
                if getattr(e, "preempted", False):
                    logger.warning(
                        "gang preempted (%s); rescheduling onto a fresh "
                        "placement group from drain checkpoint %s", e, last)
                else:
                    logger.warning(
                        "training attempt %d failed (%s); restarting gang "
                        "from checkpoint %s", attempts, e, last)

    def _run_attempt(self, storage: StorageContext,
                     latest_checkpoint: Optional[Checkpoint],
                     name: str, trial_id: str) -> Result:
        sc = self.scaling_config
        executor = BackendExecutor(
            self.backend_config,
            sc.num_workers,
            sc._resources_per_worker_not_none,
            sc.placement_strategy,
            bundles=sc.worker_bundles(),
        )
        executor.start()
        try:
            train_fn = self._wrap_train_fn()
            executor.start_training(
                train_fn, self.train_loop_config, storage,
                latest_checkpoint=latest_checkpoint,
                experiment_name=name, trial_id=trial_id,
            )
            last_metrics: Optional[Dict[str, Any]] = None
            ckpt_cfg = self.run_config.checkpoint_config
            scores: Dict[str, float] = {}
            best: list = []
            while True:
                results = executor.get_next_results()
                if results is None:
                    break
                rank0 = results[0]
                last_metrics = rank0["metrics"]
                storage.append_result(last_metrics)
                cname = rank0["checkpoint_dir_name"]
                if cname:
                    attr = ckpt_cfg.checkpoint_score_attribute
                    if attr and attr in last_metrics:
                        scores[cname] = float(last_metrics[attr])
                    best.append((Checkpoint(storage.checkpoint_path(cname)),
                                 dict(last_metrics)))
                    storage.prune_checkpoints(
                        ckpt_cfg.num_to_keep, scores,
                        ckpt_cfg.checkpoint_score_order)
            executor.finish()
            last_ckpt_path = storage.latest_checkpoint()
            return Result(
                metrics=last_metrics,
                checkpoint=Checkpoint(last_ckpt_path) if last_ckpt_path else None,
                path=storage.trial_dir,
                best_checkpoints=[
                    bc for bc in best
                    if bc[0].path == storage.checkpoint_path(
                        bc[0].path.rsplit("/", 1)[-1])
                ] or best,
            )
        finally:
            executor.shutdown()

    def _wrap_train_fn(self) -> Callable:
        fn = self.train_loop_per_worker
        datasets = self.datasets

        if not datasets:
            return fn

        def wrapped(config):
            from ray_tpu.train._internal import dataset_integration

            dataset_integration.set_dataset_shards(datasets)
            import inspect

            if len(inspect.signature(fn).parameters) == 0:
                fn()
            else:
                fn(config)

        return wrapped


class JaxTrainer(DataParallelTrainer):
    """Flagship trainer: SPMD JAX gang over the TPU mesh (SURVEY §7
    'JaxTrainer whose train loop is a jax.jit step with NamedSharding').

    Mesh-native mode: pass ``mesh_config=MeshConfig(dp=..., fsdp=...,
    tp=...)`` (or set it on ``jax_config``) and every gang worker
    bootstraps the named mesh before train_fn runs — the train loop builds
    its jit step over ``ray_tpu.train.get_mesh()`` with the canonical
    per-parameter PartitionSpecs from ``parallel.sharding`` (see
    ``train.step.init_train_state`` / ``make_train_step``: donated
    buffers, fsdp-sharded optimizer state).
    """

    _default_backend_config = JaxConfig()

    def __init__(self, train_loop_per_worker, *, jax_config=None,
                 mesh_config=None, **kwargs):
        import dataclasses

        if jax_config is not None and "backend_config" in kwargs:
            raise ValueError(
                "pass jax_config or backend_config, not both")
        cfg = (jax_config or kwargs.pop("backend_config", None)
               or JaxConfig())
        if mesh_config is not None:
            cfg = dataclasses.replace(cfg, mesh_config=mesh_config)
        kwargs["backend_config"] = cfg
        super().__init__(train_loop_per_worker, **kwargs)


class TorchTrainer(DataParallelTrainer):
    """Host-side torch (gloo) trainer for CPU-bound torch workloads."""

    def __init__(self, train_loop_per_worker, *, torch_config=None, **kwargs):
        from ray_tpu.train.backend import TorchConfig

        kwargs.setdefault("backend_config", torch_config or TorchConfig())
        super().__init__(train_loop_per_worker, **kwargs)
