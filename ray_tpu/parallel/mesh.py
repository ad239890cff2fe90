"""Device mesh construction + multi-host bootstrap.

The TPU-native replacement for the reference's NCCL process-group bootstrap
(ray: python/ray/train/torch/config.py:112 _setup_torch_process_group, and
ray/util/collective's NCCL groups): instead of exchanging NCCL unique ids,
worker gangs call `initialize_distributed` (a thin `jax.distributed` wrapper
whose coordinator is the rank-0 worker), then every process builds the same
`jax.sharding.Mesh` over the global device set and runs the same jit program
— collectives are emitted by XLA over ICI/DCN (SURVEY.md §5 "Distributed
communication backend").

Mesh axes (outer → inner, DCN-ish → ICI-ish): pp, dp, fsdp, ep, sp, tp.
TP innermost so its collectives ride the fastest ICI links.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Optional, Tuple

logger = logging.getLogger(__name__)

AXIS_ORDER = ("pp", "dp", "fsdp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Degrees for each parallelism axis; -1 on dp means 'fill remaining'."""

    dp: int = -1      # data parallel (pure replication of params)
    fsdp: int = 1     # fully-sharded data parallel (params sharded on batch axis)
    tp: int = 1       # tensor (Megatron) parallel
    sp: int = 1       # sequence/context parallel (ring attention)
    pp: int = 1       # pipeline parallel
    ep: int = 1       # expert parallel (MoE)

    def resolved(self, n_devices: int) -> "MeshConfig":
        known = self.fsdp * self.tp * self.sp * self.pp * self.ep
        dp = self.dp
        if dp == -1:
            if n_devices % known != 0:
                raise ValueError(
                    f"device count {n_devices} not divisible by "
                    f"fsdp*tp*sp*pp*ep={known}"
                )
            dp = n_devices // known
        if dp * known != n_devices:
            raise ValueError(
                f"mesh {self} needs {dp * known} devices, have {n_devices}"
            )
        return dataclasses.replace(self, dp=dp)

    def axis_sizes(self) -> Dict[str, int]:
        return {
            "pp": self.pp, "dp": self.dp, "fsdp": self.fsdp,
            "ep": self.ep, "sp": self.sp, "tp": self.tp,
        }


def build_mesh(config: MeshConfig = MeshConfig(), devices=None):
    """Build a jax.sharding.Mesh over the (global) device set.

    Devices are reshaped in list order, tp innermost. On a v5e 2x2 host
    `jax.devices()` is id 0-3 at coords (0,0) (1,0) (0,1) (1,1), so every
    tp pair and every fsdp pair of the (fsdp=2, tp=2) mesh is a pair of
    physical neighbours; chip_smoke.py asserts that on the chip. Larger
    or wrapped topologies should go through
    `jax.experimental.mesh_utils.create_device_mesh` instead."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    config = config.resolved(len(devices))
    sizes = config.axis_sizes()
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    import numpy as np

    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def build_multislice_mesh(config: MeshConfig = MeshConfig(),
                          num_slices: int = 1, devices=None):
    """Mesh spanning multiple TPU slices: a leading `dcn` axis maps onto
    the slow inter-slice network, and the per-slice MeshConfig axes map
    onto each slice's ICI torus.

    Layout doctrine (SURVEY §7 "Multi-slice (DCN) collectives"): only DATA
    parallelism crosses slices — its per-step collective is one gradient
    all-reduce, which XLA's multi-slice lowering runs hierarchically
    (reduce-scatter on ICI per slice -> small cross-slice DCN all-reduce ->
    all-gather on ICI). Model axes (tp/sp/fsdp/pp/ep) stay inside a slice,
    so their frequent collectives never touch DCN. Sharding rules map the
    batch axis over ("dcn", "dp", "fsdp") — size-1 axes drop out, so the
    same model code runs on single-slice meshes unchanged.

    Device order: on real multi-slice TPU, jax.devices() groups by
    slice_index; `jax.experimental.mesh_utils.create_hybrid_device_mesh`
    orders granules DCN-outer. Where slice structure is unavailable (CPU
    tests, single-slice), a plain reshape produces the same logical layout.
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if num_slices <= 1:
        return build_mesh(config, devices=devices)
    if len(devices) % num_slices != 0:
        raise ValueError(
            f"{len(devices)} devices not divisible by {num_slices} slices")
    per_slice = len(devices) // num_slices
    config = config.resolved(per_slice)
    sizes = config.axis_sizes()
    ici_shape = tuple(sizes[a] for a in AXIS_ORDER)
    axes = ("dcn",) + AXIS_ORDER
    if getattr(devices[0], "slice_index", None) is not None:
        # real multi-slice hardware: the hybrid util orders granules by
        # slice. Errors here are REAL config mistakes (num_slices vs the
        # actual slice count, granule mismatch) and must propagate — a
        # silent reshape fallback would run tp/sp collectives over DCN.
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_hybrid_device_mesh(
            (1,) + ici_shape,  # per-granule (per-slice) ICI shape
            (num_slices,) + (1,) * len(AXIS_ORDER),  # DCN split: dcn axis
            devices=devices)
        dev_array = np.asarray(dev_array).reshape(
            (num_slices,) + ici_shape)
    else:
        # no slice metadata (CPU tests / single-slice): plain reshape
        # yields the same logical layout
        dev_array = np.asarray(devices).reshape((num_slices,) + ici_shape)
    return Mesh(dev_array, axes)


def local_device_mesh(config: Optional[MeshConfig] = None):
    """Mesh over this process's local devices only (single-host)."""
    import jax

    return build_mesh(config or MeshConfig(), devices=jax.local_devices())


def initialize_distributed(
    coordinator_address: str, num_processes: int, process_id: int
) -> None:
    """Multi-host rendezvous: the mesh-collective equivalent of NCCL init.

    Called by every worker in a gang (see ray_tpu.train's backend setup);
    rank 0's address is distributed through the actor gang the same way the
    reference broadcasts the master address (torch/config.py:112).

    Idempotent: re-initializing an already-connected process with the same
    (coordinator, world, rank) is a no-op — a gang restarted inside a
    surviving worker process must not crash on double-init. A DIFFERENT
    binding (a re-formed gang with a new rank-0 coordinator) shuts the old
    client down first, so the process never stays silently bound to a dead
    coordinator. Limitation: a coordinator that died and RESTARTED at the
    same fixed address is indistinguishable from a live one by address
    alone — pin coordinator_port only when worker processes cannot outlive
    a gang incarnation (the default random-port path never collides).
    """
    import jax

    # jax has no public accessor for the coordinator a process is bound
    # to (jax.distributed.is_initialized() only says whether): read the
    # global client state.
    from jax._src import distributed as _dist

    state = _dist.global_state
    if state.client is not None:
        if (state.coordinator_address == coordinator_address
                and state.num_processes == num_processes
                and state.process_id == process_id):
            logger.info(
                "jax.distributed already initialized for this gang; "
                "skipping")
            return
        logger.warning(
            "jax.distributed bound to %s (world=%s rank=%s); "
            "re-initializing for %s (world=%s rank=%s)",
            state.coordinator_address, state.num_processes,
            state.process_id, coordinator_address, num_processes,
            process_id)
        state.shutdown()
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def axis_plan(n_devices: int) -> Dict[str, int]:
    """Split n devices over the (dp, fsdp, tp) named mesh, model axes
    first (tp rides the fastest links, then fsdp shards params, remainder
    is pure data parallel): 8 -> dp=2, fsdp=2, tp=2; 4 -> fsdp=2, tp=2;
    2 -> tp=2; odd prime counts fall back to pure dp."""
    plan = {"dp": 1, "fsdp": 1, "tp": 1}
    rest = n_devices
    for axis in ("tp", "fsdp"):
        if rest % 2 == 0:
            plan[axis] = 2
            rest //= 2
    plan["dp"] = rest
    return plan
