"""TPU slice/topology detection → node resources + labels.

Re-design of the reference's TPU accelerator manager
(ray python/ray/_private/accelerators/tpu.py:75-210): detect the slice this
host belongs to from GKE-injected env vars or the GCE metadata server, then
advertise

- ``TPU``: chips on this host (schedulable like any resource),
- ``TPU-<type>-head``: 1.0, on worker 0 of the slice only — the gang
  resource a job reserves to claim the whole slice,

and node labels (slice name / accelerator type / worker id) that the GCS
placement-group manager uses to keep a TPU gang on a SINGLE slice (one ICI
domain) — see gcs/pg_manager.py. On hosts with no TPU markers this is a
no-op, so CPU nodes are unaffected.

The chip count comes from the host's device nodes when it has any
(``count_local_chips``): environment variables describe the slice type,
not what this machine holds (the one-chip v5e host exports
``TPU_CHIPS_PER_HOST_BOUNDS=2,2,1``). None of this imports jax: the raylet
must not open the chips its workers will need.
"""

from __future__ import annotations

import dataclasses
import errno
import logging
import os
import re
import threading
import time
from typing import Dict, Mapping, Optional, Sequence

logger = logging.getLogger(__name__)

# Node label keys (exposed via state API / used by PG slice placement).
SLICE_NAME_LABEL = "ray.io/tpu-slice-name"
ACCELERATOR_TYPE_LABEL = "ray.io/tpu-accelerator-type"
WORKER_ID_LABEL = "ray.io/tpu-worker-id"

# GKE injects these into TPU pods (reference tpu.py: TPU_WORKER_ID,
# TPU_ACCELERATOR_TYPE, TPU_WORKER_HOSTNAMES, TPU_NAME).
_GKE_WORKER_ID = "TPU_WORKER_ID"
_GKE_ACCEL_TYPE = "TPU_ACCELERATOR_TYPE"
_GKE_HOSTNAMES = "TPU_WORKER_HOSTNAMES"
_GKE_NAME = "TPU_NAME"
_CHIP_BOUNDS = "TPU_CHIPS_PER_HOST_BOUNDS"  # e.g. "2,2,1" -> 4 chips
_VISIBLE_CHIPS = "TPU_VISIBLE_CHIPS"        # e.g. "0,1,2,3"
_HOST_BOUNDS = "TPU_HOST_BOUNDS"

_GCE_METADATA_URL = "http://metadata.google.internal/computeMetadata/v1"


@dataclasses.dataclass(frozen=True)
class TpuSliceInfo:
    accelerator_type: str      # e.g. "v5litepod-16", "v4-8"
    slice_name: str            # unique per slice (TPU_NAME / instance name)
    worker_id: int             # this host's index within the slice
    num_chips: int             # chips on THIS host
    num_workers: int           # hosts in the slice (1 if unknown)

    @property
    def is_head(self) -> bool:
        return self.worker_id == 0


def tpu_head_resource_name(accelerator_type: str) -> str:
    """Gang resource advertised by worker 0 of a slice (reference
    tpu.py: `TPU-{v4-8}-head` pod resource)."""
    return f"TPU-{accelerator_type}-head"


# Published per-chip peaks by jax ``device_kind`` (Google Cloud TPU
# documentation, system-architecture page of each generation; v2/v3
# per-chip = 2 cores). The tables every MFU and roofline figure in the
# repo divides by: a device that is not listed is an error, never a
# default. HBM bandwidth is listed only where the repo has measured.
_BF16_PEAK_FLOPS = {
    "TPU v2": 46e12,
    "TPU v3": 123e12,
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}
_HBM_PEAK_BYTES_PER_SEC = {
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
}


def _published_peak(table: Dict[str, float], device_kind: str) -> float:
    try:
        return table[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device_kind {device_kind!r} (listed: "
            f"{sorted(table)}); utilization against a guessed peak is not "
            "a measurement — add the device, with its source, to "
            "ray_tpu/_private/accelerators/tpu.py") from None


def bf16_peak_flops_per_chip(device_kind: str) -> float:
    """Per-chip bf16 peak FLOP/s for the given jax ``device_kind``; raises
    ValueError for a device the table does not list."""
    return _published_peak(_BF16_PEAK_FLOPS, device_kind)


def hbm_peak_bytes_per_sec(device_kind: str) -> float:
    """Per-chip HBM bandwidth for the given jax ``device_kind``; raises
    ValueError for a device the table does not list."""
    return _published_peak(_HBM_PEAK_BYTES_PER_SEC, device_kind)


def count_local_chips(dev_root: str = "/dev") -> int:
    """TPU chips this host holds, read from its device nodes without
    opening them: ``accel<N>`` entries, else numbered VFIO groups
    (``vfio/<N>``; ``vfio/vfio`` is the container node, not a chip). The
    v5e hosts this repo runs on expose the latter."""
    try:
        accel = [n for n in os.listdir(dev_root)
                 if re.fullmatch(r"accel\d+", n)]
    except OSError:
        return 0
    if accel:
        return len(accel)
    try:
        return sum(n.isdigit()
                   for n in os.listdir(os.path.join(dev_root, "vfio")))
    except OSError:
        return 0


def wait_until_chips_free(timeout_s: float = 90.0, dev_root: str = "/dev",
                          poll_s: float = 0.25) -> None:
    """Before this process first opens the backend: wait while another
    process still holds a VFIO group of the chips it may use (its
    ``TPU_VISIBLE_CHIPS``, else every numbered group), at most
    ``timeout_s``. A group has one opener at a time; the one before us is
    as a rule a killed worker whose threads are still letting go of the
    device, seconds after its main thread reads as gone (on four chips the
    next process found ``/dev/vfio/<n>`` busy and libtpu gave up at once).
    Never imports jax, never raises, and returns at once where there is
    nothing to wait for: a process told to stay on the CPU, no VFIO nodes,
    a node it may not open, or one it holds itself."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] == "cpu":
        return
    try:
        vfio = os.path.join(dev_root, "vfio")
        groups = sorted((n for n in os.listdir(vfio) if n.isdigit()), key=int)
        visible = os.environ.get(_VISIBLE_CHIPS)
        if visible:
            groups = [groups[int(c)] for c in visible.split(",")]
        paths = [os.path.join(vfio, n) for n in groups]
        mine = {os.readlink(f"/proc/self/fd/{fd}")
                for fd in os.listdir("/proc/self/fd")
                if os.path.exists(f"/proc/self/fd/{fd}")}
    except (OSError, ValueError, IndexError):
        return
    if mine.intersection(paths):
        return   # the backend is up: the holder is this process

    def busy(path: str) -> bool:
        try:
            os.close(os.open(path, os.O_RDWR))
        except OSError as e:
            return e.errno == errno.EBUSY   # any other: not ours to judge
        return False

    deadline = time.monotonic() + timeout_s
    while any(busy(p) for p in paths) and time.monotonic() < deadline:
        time.sleep(poll_s)   # past the deadline libtpu says what is wrong


# libtpu reads these when the process first opens the backend. A worker
# granted one or two chips of a larger host must describe a one-process
# "host" of that size, or libtpu waits for the rest of the advertised
# topology (reference: tpu.py set_current_process_visible_accelerator_ids,
# which likewise leaves other counts to TPU_VISIBLE_CHIPS alone).
_SUBHOST_BOUNDS = {1: "1,1,1", 2: "1,2,1"}


def visible_chips_env(chip_ids: Sequence[int],
                      chips_on_host: int) -> Dict[str, str]:
    """Environment that confines a worker process to ``chip_ids`` (indices
    into this host's chips). Empty when the worker gets the whole host:
    libtpu's own defaults are right there."""
    if not chip_ids or len(chip_ids) >= chips_on_host:
        return {}
    env = {_VISIBLE_CHIPS: ",".join(str(c) for c in chip_ids)}
    bounds = _SUBHOST_BOUNDS.get(len(chip_ids))
    if bounds:
        env[_CHIP_BOUNDS] = bounds
        env[_HOST_BOUNDS] = "1,1,1"
    return env


def chips_per_host(accelerator_type: str,
                   env: Optional[Mapping[str, str]] = None) -> int:
    """Chips a single host of this slice type contributes — the per-worker
    `TPU` demand a ScalingConfig(topology=...) gang bundles up. Defaults to
    os.environ (like detect_tpu) so TPU_CHIPS_PER_HOST_BOUNDS overrides are
    honored — the demand must match what apply_tpu_detection advertises."""
    return _chips_per_host(os.environ if env is None else env,
                           accelerator_type)


def _chips_per_host(env: Mapping[str, str], accelerator_type: str) -> int:
    bounds = env.get(_CHIP_BOUNDS)
    if bounds:
        try:
            n = 1
            for part in bounds.split(","):
                n *= int(part)
            return n
        except ValueError:
            pass
    visible = env.get(_VISIBLE_CHIPS)
    if visible:
        return len([c for c in visible.split(",") if c.strip()])
    # Generation defaults (reference: 4 chips/host; single-host v5e/v6e
    # slices put all chips on the one host).
    try:
        total = int(accelerator_type.rsplit("-", 1)[1])
    except (IndexError, ValueError):
        return 4
    gen = accelerator_type.split("-", 1)[0].lower()
    if gen in ("v5litepod", "v5e", "v6e") and total <= 8:
        return total
    # v2/v3/v4/v5p: 4 chips per host; accelerator_type counts cores for
    # v2-v3 (8 cores/host) and chips for v4+ — either way min() caps the
    # single-host case.
    return min(4, total)


def _gce_metadata(path: str, timeout: float = 0.5) -> Optional[str]:
    """Best-effort GCE metadata read (absent off-GCP; never raises). The
    socket timeout does not cover name resolution, which can hang on a
    host with no network, so the request runs on a daemon thread that is
    abandoned after ``2 * timeout``: node start-up never waits longer."""
    result = []

    def fetch():
        try:
            import urllib.request

            req = urllib.request.Request(
                f"{_GCE_METADATA_URL}/{path}",
                headers={"Metadata-Flavor": "Google"})
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                result.append(resp.read().decode())
        except Exception:  # noqa: BLE001 — any failure means "not on GCE"
            pass

    t = threading.Thread(target=fetch, daemon=True, name="gce-metadata")
    t.start()
    t.join(2 * timeout)
    return result[0] if result else None


def detect_tpu(env: Optional[Mapping[str, str]] = None,
               probe_gce: bool = False) -> Optional[TpuSliceInfo]:
    """Detect this host's TPU slice membership.

    Detection sources, in order (reference tpu.py:75-210):
    1. GKE env vars (``TPU_WORKER_ID`` / ``TPU_ACCELERATOR_TYPE`` / ...).
    2. The GCE metadata server (only when ``probe_gce`` — it costs a network
       round-trip and is meaningless off-GCP).

    Returns None on non-TPU hosts.
    """
    env = os.environ if env is None else env

    accel_type = env.get(_GKE_ACCEL_TYPE)
    if accel_type:
        worker_id = _parse_worker_id(env.get(_GKE_WORKER_ID))
        hostnames = [h for h in env.get(_GKE_HOSTNAMES, "").split(",") if h]
        slice_name = env.get(_GKE_NAME) or (
            hostnames[0] if hostnames else f"tpu-{accel_type}")
        return TpuSliceInfo(
            accelerator_type=accel_type,
            slice_name=slice_name,
            worker_id=worker_id,
            num_chips=_chips_per_host(env, accel_type),
            num_workers=max(1, len(hostnames)),
        )

    if probe_gce:
        return _probe_gce_cached(env)
    return None


_GCE_PROBE_RESULT = ...  # Ellipsis = not probed yet (None is a valid result)


def _probe_gce_cached(env) -> Optional[TpuSliceInfo]:
    """One metadata probe per process: several raylets/inits in one process
    (tests, head node) must not each pay the network round trip."""
    global _GCE_PROBE_RESULT
    if _GCE_PROBE_RESULT is not ...:
        return _GCE_PROBE_RESULT
    _GCE_PROBE_RESULT = _probe_gce(env)
    return _GCE_PROBE_RESULT


def _probe_gce(env) -> Optional[TpuSliceInfo]:
    accel_type = _gce_metadata("instance/attributes/accelerator-type")
    if not accel_type:
        return None
    worker_str = _gce_metadata(
        "instance/attributes/agent-worker-number") or "0"
    name = (_gce_metadata("instance/attributes/instance-id")
            or _gce_metadata("instance/name")
            or f"tpu-{accel_type}")
    return TpuSliceInfo(
        accelerator_type=accel_type,
        slice_name=name,
        worker_id=_parse_worker_id(worker_str),
        num_chips=_chips_per_host(env, accel_type),
        num_workers=1,
    )


def _parse_worker_id(raw) -> int:
    """Tolerant parse: a garbled TPU_WORKER_ID must degrade (worker 0, with
    a warning), not crash node startup — detection is supposed to be a
    no-op-or-better on any host."""
    if not raw:
        return 0
    try:
        return int(str(raw).strip())
    except ValueError:
        logger.warning("unparseable TPU worker id %r; assuming 0", raw)
        return 0


def apply_tpu_detection(
    resources: Dict[str, float],
    labels: Dict[str, str],
    env: Optional[Mapping[str, str]] = None,
    probe_gce: bool = False,
    dev_root: Optional[str] = None,
) -> Optional[TpuSliceInfo]:
    """Merge detected TPU resources/labels into a node's advertisement.

    ``dev_root`` (``"/dev"`` for a real node; None for in-process test
    nodes that model other hosts) makes the host's device nodes the chip
    count: they override what the slice type implies, and a plain host
    with chips but no slice identity still advertises ``TPU``. Without
    device nodes there is nothing to schedule, so the metadata server is
    not asked either.

    Explicit user-set values win (a node started with ``resources={"TPU": 8}``
    keeps 8). Mutates both dicts in place; returns the detection result.
    """
    chips = count_local_chips(dev_root) if dev_root else 0
    info = detect_tpu(
        env, probe_gce=probe_gce and (chips > 0 or not dev_root))
    if info is None:
        if chips:
            resources.setdefault("TPU", float(chips))
            logger.info("TPU host without slice identity: %d chips", chips)
        return None
    if chips:
        info = dataclasses.replace(info, num_chips=chips)
    resources.setdefault("TPU", float(info.num_chips))
    # Typed per-chip resource alongside the generic one: gangs that pin a
    # topology (ScalingConfig(topology="v5e-8")) demand `TPU-v5e-8` per
    # worker so they can only place on hosts of that slice generation.
    resources.setdefault(f"TPU-{info.accelerator_type}",
                         float(info.num_chips))
    if info.is_head:
        resources.setdefault(
            tpu_head_resource_name(info.accelerator_type), 1.0)
    labels.setdefault(SLICE_NAME_LABEL, info.slice_name)
    labels.setdefault(ACCELERATOR_TYPE_LABEL, info.accelerator_type)
    labels.setdefault(WORKER_ID_LABEL, str(info.worker_id))
    logger.info(
        "TPU slice detected: %s worker %d (%d chips/host)",
        info.slice_name, info.worker_id, info.num_chips)
    return info
