"""Pluggable training backends (reference: ray python/ray/train/backend.py:32
— Backend.on_start/on_training_start/on_shutdown hooks; torch/config.py:112
replaced by JAX distributed rendezvous).

JaxBackend is the TPU-native analogue of the reference's NCCL process-group
bootstrap: rank 0 publishes its host as the `jax.distributed` coordinator,
every worker calls `jax.distributed.initialize(coordinator, world_size,
rank)`, and from then on `jax.devices()` spans the whole gang — mesh
construction and collectives are compiler-emitted over ICI/DCN (SURVEY §2.3
"TPU-native equivalent" column).
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, Optional

from ray_tpu._private.device_profiler import (
    delta, install_compile_listener, merge, snapshot, span)

logger = logging.getLogger(__name__)


class BackendConfig:
    @property
    def backend_cls(self):
        return Backend


class Backend:
    """Hooks run on the driver around the worker gang's lifecycle."""

    share_cuda_visible_devices: bool = False

    def on_start(self, worker_group, backend_config: BackendConfig) -> None:
        pass

    def on_training_start(self, worker_group, backend_config: BackendConfig) -> None:
        pass

    def on_shutdown(self, worker_group, backend_config: BackendConfig) -> None:
        pass


@dataclasses.dataclass
class JaxConfig(BackendConfig):
    """distributed=True bootstraps jax.distributed across the gang (multi-
    host TPU). On a single host (or under tests on the CPU platform) leave it
    False: every worker sees the local chips only.

    mesh_config (a ``ray_tpu.parallel.MeshConfig``) switches the gang into
    MESH-NATIVE mode: every worker bootstraps the named (dp, fsdp, tp, ...)
    mesh through the collective-group rendezvous (util.collective.
    bootstrap_mesh — with distributed=True the rendezvous also feeds
    jax.distributed.initialize, replacing the metadata-exchange coordinator
    below), and train_fns reach it via ``ray_tpu.train.get_mesh()``.
    """

    distributed: bool = False
    coordinator_port: int = 0
    platform: Optional[str] = None  # force e.g. "cpu" in tests
    # Applied in each worker BEFORE its first jax import (e.g. XLA_FLAGS
    # to fake per-process device counts in multi-process CPU tests).
    env_vars: Optional[dict] = None
    # Mesh-native mode: the gang's parallelism axes (MeshConfig). None =
    # legacy per-worker loops with no ambient mesh.
    mesh_config: Optional[Any] = None
    num_slices: int = 1

    @property
    def backend_cls(self):
        return JaxBackend


def _find_free_port() -> int:
    # module-level so worker_group.execute_single can ship it by reference
    from ray_tpu._private.rpc import find_free_port

    return find_free_port()


def _init_jax_worker(platform: Optional[str], coordinator: Optional[str],
                     world_size: int, rank: int,
                     env_vars: Optional[dict] = None) -> None:
    """First thing a gang worker runs, before anything touches the
    backend: jax.distributed.initialize (here, or later from the mesh
    rendezvous) refuses to run after any jax computation."""
    import os

    from ray_tpu._private import compile_cache

    for k, v in (env_vars or {}).items():
        os.environ[k] = v
    if platform:
        os.environ["JAX_PLATFORMS"] = platform
    compile_cache.enable()
    if coordinator is not None:
        import jax

        with span("train.worker.distributed_init"):
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=world_size,
                process_id=rank,
            )


def _worker_platform() -> str:
    import jax

    from ray_tpu.train.spmd import wait_for_chips

    wait_for_chips()   # returns at once where the backend is up
    # Outside mesh mode this is the first call that brings the backend
    # up (seconds on a chip); after a mesh rendezvous it is microseconds.
    with span("train.worker.open_chip"):
        return jax.devices()[0].platform


def _spanned(fn, *args, **kwargs):
    """In a gang worker: one round's call, and beside its result the
    `train.worker.*` spans it left (not what the process's background
    threads did meanwhile). The driver waits for the result anyway, so
    the spans reach it with no round trip of their own."""
    before = snapshot()
    out = fn(*args, **kwargs)
    left = delta(snapshot(), before)["spans"]
    return out, {"spans": {name: agg for name, agg in left.items()
                           if name.startswith("train.worker.")}}


def _round(worker_group, name: str, fn, kwargs_of=lambda rank: {}) -> list:
    """One round of remote calls, `fn(**kwargs_of(rank))` on every rank,
    under the driver's span `name`; rank 0's worker-side spans are merged
    under it, so its self time is what the workers did not account for."""
    import ray_tpu

    with span(name, world=worker_group.num_workers):
        outs = ray_tpu.get([
            worker.execute.remote(_spanned, fn, **kwargs_of(rank))
            for rank, worker in enumerate(worker_group.workers)])
        merge(outs[0][1])
    return [out for out, _ in outs]


class JaxBackend(Backend):
    def on_start(self, worker_group, backend_config: JaxConfig) -> None:
        world = worker_group.num_workers
        coordinator = None
        mesh_mode = backend_config.mesh_config is not None
        if mesh_mode and world > 1 and not backend_config.distributed:
            # Without jax.distributed each worker would bootstrap its OWN
            # local mesh (identical shapes, so the agreement check below
            # cannot catch it) and train a divergent model copy with no
            # cross-worker sync at all — silently wrong results.
            raise ValueError(
                "mesh_config with num_workers>1 requires "
                "JaxConfig(distributed=True): a multi-worker gang must "
                "rendezvous into ONE global mesh; distributed=False would "
                f"give {world} workers {world} independent local meshes "
                "with no gradient sync")
        if backend_config.distributed and world > 1 and not mesh_mode:
            # mesh-native gangs rendezvous through the collective group
            # below instead of exchanging the coordinator via gang metadata
            meta = worker_group.group_metadata()
            port = backend_config.coordinator_port or worker_group.execute_single(
                0, _find_free_port)
            coordinator = f"{meta[0]['hostname']}:{port}"
            logger.info("jax.distributed coordinator at %s", coordinator)
        _round(worker_group, "train.gang.backend_init", _init_jax_worker,
               lambda rank: dict(
                   platform=backend_config.platform, coordinator=coordinator,
                   world_size=world, rank=rank,
                   env_vars=backend_config.env_vars))
        if mesh_mode:
            import uuid

            from ray_tpu.train.spmd import setup_worker_mesh

            group = f"rt_train_mesh:{uuid.uuid4().hex[:8]}"
            self._mesh_group = group
            shapes = _round(
                worker_group, "train.gang.mesh", setup_worker_mesh,
                lambda rank: dict(
                    mesh_config=backend_config.mesh_config,
                    group_name=group, world_size=world, rank=rank,
                    distributed=backend_config.distributed,
                    num_slices=backend_config.num_slices,
                    coordinator_port=backend_config.coordinator_port))
            if len(set(map(str, shapes))) != 1:
                raise RuntimeError(
                    f"gang workers disagree on mesh shape: {shapes}")
            logger.info("gang mesh established: %s", shapes[0])
        # A gang that was given chips must be computing on them. Checked
        # once the backend is up (after the mesh rendezvous, which must
        # precede it): a worker that came up on another platform would
        # otherwise train on, at a fraction of the speed, under the
        # device's name.
        expected = backend_config.platform or (
            "tpu" if worker_group.demands_tpu else None)
        platforms = _round(worker_group, "train.gang.platform_check",
                           _worker_platform)
        if expected and any(p != expected for p in platforms):
            raise RuntimeError(
                f"gang asked for platform {expected!r} but its workers "
                f"report {platforms}: check JAX_PLATFORMS in the workers' "
                "environment and that the chips are not held by another "
                "process")

    def on_training_start(self, worker_group,
                          backend_config: JaxConfig) -> None:
        # after `init_session`: the gang's jits and its hosts' full
        # collections are timed whatever `train_fn` does about it, and are
        # part of what `finish()` hands back
        try:
            worker_group.execute(install_compile_listener)
        except Exception:  # noqa: BLE001 — the record is no part of the run
            logger.debug("no jit listener in the gang", exc_info=True)

    def on_shutdown(self, worker_group, backend_config: JaxConfig) -> None:
        if backend_config.mesh_config is None:
            return
        from ray_tpu.train.spmd import teardown_worker_mesh

        try:
            worker_group.execute(teardown_worker_mesh)
        except Exception:  # noqa: BLE001 — teardown best-effort
            logger.debug("mesh teardown failed", exc_info=True)
        # Worker-side teardown kills the detached rendezvous coordinator
        # from rank 0 — but a dead rank 0 (the very failure that triggers a
        # gang restart) would leak it, and each restart uses a fresh group
        # name, so orphans would accumulate. The driver sweeps it too.
        group = getattr(self, "_mesh_group", None)
        if group is not None:
            import ray_tpu

            from ray_tpu.util.collective.collective import _COORD_PREFIX

            self._mesh_group = None
            try:
                ray_tpu.kill(ray_tpu.get_actor(_COORD_PREFIX + group))
            except ValueError:
                pass  # never created (world-1 gang) or already dead


@dataclasses.dataclass
class TorchConfig(BackendConfig):
    """CPU torch.distributed (gloo) rendezvous for torch-based train_fns —
    the reference's Train torch backend (torch/config.py:35) without CUDA:
    on TPU fleets torch runs host-side (data preprocessing, eval harnesses).
    """

    backend: str = "gloo"
    init_timeout_s: int = 300

    @property
    def backend_cls(self):
        return TorchBackend


def _init_torch_pg(backend: str, init_method: str, world_size: int,
                   rank: int, timeout_s: int) -> None:
    import datetime

    import torch.distributed as dist

    if dist.is_initialized():
        return
    dist.init_process_group(
        backend=backend, init_method=init_method,
        world_size=world_size, rank=rank,
        timeout=datetime.timedelta(seconds=timeout_s),
    )


def _destroy_torch_pg() -> None:
    import torch.distributed as dist

    if dist.is_initialized():
        dist.destroy_process_group()


class TorchBackend(Backend):
    def on_start(self, worker_group, backend_config: TorchConfig) -> None:
        world = worker_group.num_workers
        meta = worker_group.group_metadata()
        port = worker_group.execute_single(0, _find_free_port)
        init_method = f"tcp://{meta[0]['hostname']}:{port}"
        import ray_tpu

        ray_tpu.get([
            worker_group.workers[rank].execute.remote(
                _init_torch_pg, backend_config.backend, init_method,
                world, rank, backend_config.init_timeout_s)
            for rank in range(world)
        ])

    def on_shutdown(self, worker_group, backend_config: TorchConfig) -> None:
        try:
            worker_group.execute(_destroy_torch_pg)
        except Exception:  # noqa: BLE001 — teardown best-effort
            pass
