"""Distributed training library (JaxTrainer and friends).

Reference counterpart: Ray Train (ray: python/ray/train — BaseTrainer.fit
base_trainer.py:567, DataParallelTrainer, BackendExecutor, WorkerGroup,
session report/get_checkpoint/get_context session.py:666/:753/context.py:80),
with the NCCL backend replaced by mesh construction + XLA collectives.
"""

from ray_tpu.air import (  # noqa: F401 — re-exported like ray.train does
    CheckpointConfig,
    FailureConfig,
    Result,
    RunConfig,
    ScalingConfig,
)
from ray_tpu.train._internal.dataset_integration import (  # noqa: F401
    get_dataset_shard,
)
from ray_tpu.train._internal.session import (  # noqa: F401
    GangPreemptedError,
    get_checkpoint,
    get_context,
    report,
)
from ray_tpu.train.backend import (  # noqa: F401
    Backend,
    BackendConfig,
    JaxBackend,
    JaxConfig,
    TorchBackend,
    TorchConfig,
)
from ray_tpu.train.checkpoint import Checkpoint  # noqa: F401
from ray_tpu.train.context import TrainContext  # noqa: F401
from ray_tpu.train.predictor import (  # noqa: F401
    BatchPredictor,
    JaxPredictor,
    Predictor,
    TorchPredictor,
)
from ray_tpu.train.spmd import (  # noqa: F401
    batch_sharding,
    get_mesh,
    shard_local_batch,
)
from ray_tpu.train.gbdt import (  # noqa: F401
    LightGBMTrainer,
    XGBoostTrainer,
)
from ray_tpu.train.trainer import (  # noqa: F401
    BaseTrainer,
    DataParallelTrainer,
    JaxTrainer,
    TorchTrainer,
)

_STEP_NAMES = ("TrainState", "init_train_state", "make_train_step")


def __getattr__(name):
    # train.step imports jax at module level; resolving its names on first
    # use keeps `import ray_tpu.train` jax-free, so a driver that only
    # launches a JaxTrainer never loads — let alone opens — the backend
    # its workers own.
    if name in _STEP_NAMES:
        from ray_tpu.train import step

        return getattr(step, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = [
    "Backend",
    "BackendConfig",
    "BaseTrainer",
    "BatchPredictor",
    "Checkpoint",
    "CheckpointConfig",
    "DataParallelTrainer",
    "FailureConfig",
    "GangPreemptedError",
    "JaxBackend",
    "JaxConfig",
    "JaxPredictor",
    "JaxTrainer",
    "LightGBMTrainer",
    "Predictor",
    "Result",
    "RunConfig",
    "ScalingConfig",
    "TorchBackend",
    "TorchConfig",
    "TorchPredictor",
    "TorchTrainer",
    "XGBoostTrainer",
    "TrainContext",
    "TrainState",
    "batch_sharding",
    "get_checkpoint",
    "get_context",
    "get_dataset_shard",
    "get_mesh",
    "init_train_state",
    "make_train_step",
    "report",
    "shard_local_batch",
]
