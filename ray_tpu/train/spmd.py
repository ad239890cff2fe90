"""Worker-side SPMD mesh state for JaxTrainer's mesh-native mode.

When ``JaxConfig.mesh_config`` is set, every gang worker bootstraps the
named ``(dp, fsdp, tp, ...)`` mesh through the collective-group rendezvous
(``util.collective.bootstrap_mesh``) during backend setup, and the user's
train_fn reaches it with ``ray_tpu.train.get_mesh()``. A multi-worker
distributed gang (one process per host, ``jax.distributed`` across them)
and a single-process multi-device mesh run the SAME bootstrap call — the
world-1 group just skips the rendezvous leg — so train_fns written against
``get_mesh()`` move between laptops and pod slices unchanged.

The helpers below are the glue the mesh mode rests on:

- ``batch_sharding``: the canonical NamedSharding for a ``[batch, seq]``
  token batch under the logical-axis rules (batch over the data axes).
- ``shard_local_batch``: turn each process's host shard of the global
  batch into a global ``jax.Array`` without replicating the full batch on
  any host.
"""

from __future__ import annotations

import logging
import threading
from typing import Any, Dict, Optional

from ray_tpu._private.device_profiler import span

logger = logging.getLogger(__name__)

_state_lock = threading.Lock()
_state: Dict[str, Any] = {"mesh": None, "group": None}


def wait_for_chips() -> None:
    """Before the call that opens the backend, and before its span
    `train.worker.open_chip`: a gang that starts seconds after another
    process held these chips waits for them here, under a span of its own,
    instead of failing the run."""
    from ray_tpu._private.accelerators.tpu import wait_until_chips_free

    with span("train.worker.chip_wait"):
        wait_until_chips_free()


def get_mesh():
    """The gang mesh bootstrapped for this worker (None outside mesh mode).

    Inside a JaxTrainer train_fn with ``JaxConfig.mesh_config`` set, this
    is the named ``jax.sharding.Mesh`` every rank agreed on.
    """
    with _state_lock:
        return _state["mesh"]


def setup_worker_mesh(mesh_config, *, group_name: str, world_size: int,
                      rank: int, distributed: bool, num_slices: int = 1,
                      mesh_axes=None,
                      coordinator_port: int = 0) -> Dict[str, int]:
    """Bootstrap this worker's gang mesh through the collective rendezvous.

    Runs inside each gang worker (dispatched by JaxBackend.on_start).
    ``distributed=False`` gangs build per-process local meshes (world-1
    groups, no cluster traffic); ``distributed=True`` gangs rendezvous and
    build one global mesh. Returns the mesh axis sizes for driver-side
    logging.
    """
    from ray_tpu.util import collective as col

    ws, rk = ((world_size, rank) if (distributed and world_size > 1)
              else (1, 0))
    if not col.is_group_initialized(group_name):
        col.init_collective_group(ws, rk, backend="mesh",
                                  group_name=group_name, mesh_axes=mesh_axes)
    if ws == 1:
        import jax

        # In a process that has computed nothing yet this is the call that
        # brings the backend up: 8-14 s where that opens a chip.
        wait_for_chips()
        with span("train.worker.open_chip"):
            devices = jax.devices()
        with span("train.worker.mesh_build", devices=len(devices)):
            mesh = col.bootstrap_mesh(mesh_config, group_name=group_name,
                                      devices=devices, num_slices=num_slices)
    else:
        # jax.distributed.initialize has to precede the backend, and
        # bootstrap_mesh does both behind its rendezvous: seen from here
        # the backend comes up inside this one call
        wait_for_chips()
        with span("train.worker.open_chip", world=ws):
            mesh = col.bootstrap_mesh(mesh_config, group_name=group_name,
                                      num_slices=num_slices,
                                      coordinator_port=coordinator_port)
    with _state_lock:
        _state["mesh"] = mesh
        _state["group"] = group_name
    return {str(a): int(s) for a, s in mesh.shape.items()}


def teardown_worker_mesh() -> None:
    from ray_tpu.util import collective as col

    with _state_lock:
        group = _state["group"]
        _state["mesh"] = None
        _state["group"] = None
    if group is not None and col.is_group_initialized(group):
        col.destroy_collective_group(group)


def batch_sharding(mesh=None, rules=None, logical=("batch", "seq")):
    """NamedSharding for a global token batch on the (gang) mesh."""
    from ray_tpu.parallel.sharding import LogicalAxisRules, logical_sharding

    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None:
        raise RuntimeError(
            "batch_sharding needs a mesh: pass one, or run inside a "
            "JaxTrainer worker with JaxConfig.mesh_config set")
    return logical_sharding(mesh, logical, rules or LogicalAxisRules())


def shard_local_batch(batch: Dict[str, Any], sharding) -> Dict[str, Any]:
    """Assemble global arrays from this process's host shard of the batch.

    Each gang process passes only the rows it owns; the shared assembly
    helper (``data.dataset._shard_host_batch`` — the same one
    ``iter_jax_batches(sharding=...)`` uses) places them on the local
    devices the sharding maps there and stitches the global array — no host
    ever materializes the full global batch (the device_put-the-whole-thing
    path would need it on every host). On a single-process mesh the rows
    ARE the global batch and land sliced per device, never replicated.
    """
    from ray_tpu.data.dataset import _shard_host_batch

    return {k: _shard_host_batch(v, sharding) for k, v in batch.items()}
