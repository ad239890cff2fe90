"""TPU slice detection + single-slice gang placement (VERDICT r1 #2).

Reference behavior: ray python/ray/_private/accelerators/tpu.py:75-210
(GKE env detection, TPU-<type>-head gang resource, chips/host); the
placement itself is TPU-first design — a STRICT_PACK TPU gang maps onto
one slice (one ICI domain) and never straddles slices.
"""

import time

import pytest

import ray_tpu
from ray_tpu._private.accelerators import (
    apply_tpu_detection,
    detect_tpu,
    tpu_head_resource_name,
)
from ray_tpu._private.accelerators.tpu import SLICE_NAME_LABEL
from ray_tpu.util.placement_group import (
    placement_group,
    placement_group_table,
    remove_placement_group,
)


def _slice_env(name: str, worker_id: int, n_hosts: int = 2,
               accel: str = "v5litepod-16"):
    hostnames = ",".join(f"{name}-w{i}" for i in range(n_hosts))
    return {
        "TPU_ACCELERATOR_TYPE": accel,
        "TPU_NAME": name,
        "TPU_WORKER_ID": str(worker_id),
        "TPU_WORKER_HOSTNAMES": hostnames,
        "TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1",
    }


# ---------------------------------------------------------------- detection

def test_detect_tpu_from_gke_env():
    info = detect_tpu(_slice_env("slice-a", worker_id=1))
    assert info is not None
    assert info.accelerator_type == "v5litepod-16"
    assert info.slice_name == "slice-a"
    assert info.worker_id == 1 and not info.is_head
    assert info.num_chips == 4  # 2*2*1 bounds
    assert info.num_workers == 2


def test_detect_tpu_absent_on_plain_host():
    assert detect_tpu({}) is None


def test_chips_per_host_defaults():
    # no bounds: single-host v5e slices put all chips on the host
    info = detect_tpu({"TPU_ACCELERATOR_TYPE": "v5litepod-8",
                       "TPU_NAME": "s"})
    assert info.num_chips == 8
    # multi-host v4: 4 chips/host
    info = detect_tpu({"TPU_ACCELERATOR_TYPE": "v4-16", "TPU_NAME": "s"})
    assert info.num_chips == 4
    # TPU_VISIBLE_CHIPS wins over generation defaults
    info = detect_tpu({"TPU_ACCELERATOR_TYPE": "v4-16", "TPU_NAME": "s",
                       "TPU_VISIBLE_CHIPS": "0,1"})
    assert info.num_chips == 2


def test_apply_tpu_detection_resources_and_labels():
    resources, labels = {}, {}
    info = apply_tpu_detection(resources, labels,
                               env=_slice_env("slice-a", worker_id=0))
    assert resources["TPU"] == 4.0
    assert resources[tpu_head_resource_name("v5litepod-16")] == 1.0
    assert labels[SLICE_NAME_LABEL] == "slice-a"
    assert info.is_head
    # non-head worker advertises chips but NOT the gang head resource
    resources2, labels2 = {}, {}
    apply_tpu_detection(resources2, labels2,
                        env=_slice_env("slice-a", worker_id=1))
    assert "TPU" in resources2
    assert tpu_head_resource_name("v5litepod-16") not in resources2
    # explicit user resources win
    resources3 = {"TPU": 8.0}
    apply_tpu_detection(resources3, {},
                        env=_slice_env("slice-a", worker_id=1))
    assert resources3["TPU"] == 8.0


def test_detect_tpu_gce_metadata_probe(monkeypatch):
    """Non-GKE GCE TPU VMs expose topology via the metadata server."""
    from ray_tpu._private.accelerators import tpu as tpu_mod

    values = {
        "instance/attributes/accelerator-type": "v5p-16",
        "instance/attributes/agent-worker-number": "1",
        "instance/attributes/instance-id": "my-tpu-vm",
    }
    monkeypatch.setattr(tpu_mod, "_gce_metadata",
                        lambda path, timeout=0.5: values.get(path))
    monkeypatch.setattr(tpu_mod, "_GCE_PROBE_RESULT", ...)
    info = detect_tpu({}, probe_gce=True)
    assert info is not None
    assert info.accelerator_type == "v5p-16"
    assert info.slice_name == "my-tpu-vm"
    assert info.worker_id == 1
    assert info.num_chips == 4
    # probe result is memoized per process
    monkeypatch.setattr(tpu_mod, "_gce_metadata",
                        lambda path, timeout=0.5: 1 / 0)
    assert detect_tpu({}, probe_gce=True).slice_name == "my-tpu-vm"


def test_chip_count_comes_from_device_nodes(tmp_path):
    """A plain TPU host (no GKE variables, no network) still advertises
    its chips; and where variables describe the slice TYPE, the device
    nodes say what THIS host holds (the one-chip v5e machine exports
    2,2,1 bounds)."""
    from ray_tpu._private.accelerators.tpu import count_local_chips

    dev = tmp_path / "dev"
    (dev / "vfio").mkdir(parents=True)
    assert count_local_chips(str(dev)) == 0
    assert count_local_chips(str(tmp_path / "missing")) == 0
    for name in ("vfio", "0", "1", "2", "3"):  # vfio/vfio: container node
        (dev / "vfio" / name).touch()
    assert count_local_chips(str(dev)) == 4
    (dev / "accel0").touch()  # accel nodes win where the host has them
    (dev / "accelerometer").touch()
    assert count_local_chips(str(dev)) == 1
    (dev / "accel0").unlink()

    resources, labels = {}, {}
    assert apply_tpu_detection(resources, labels, env={},
                               dev_root=str(dev)) is None
    assert resources == {"TPU": 4.0} and labels == {}

    one = tmp_path / "one"
    (one / "vfio").mkdir(parents=True)
    (one / "vfio" / "1").touch()
    resources = {}
    info = apply_tpu_detection(
        resources, {}, dev_root=str(one),
        env={"TPU_ACCELERATOR_TYPE": "v5litepod-4",
             "TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1", "TPU_WORKER_ID": "0"})
    assert info.num_chips == 1
    assert resources["TPU"] == 1.0 and resources["TPU-v5litepod-4"] == 1.0


def test_metadata_probe_skipped_without_chips_and_bounded(monkeypatch,
                                                           tmp_path):
    import time

    from ray_tpu._private.accelerators import tpu as tpu_mod

    asked = []
    monkeypatch.setattr(tpu_mod, "_GCE_PROBE_RESULT", ...)
    monkeypatch.setattr(tpu_mod, "_gce_metadata",
                        lambda path, timeout=0.5: asked.append(path))
    apply_tpu_detection({}, {}, env={}, probe_gce=True,
                        dev_root=str(tmp_path))
    assert asked == []  # no device nodes: nothing to ask about
    monkeypatch.undo()

    # a lookup that never returns (no network: resolution can hang past
    # any socket timeout) costs start-up a bounded wait
    import urllib.request

    monkeypatch.setattr(urllib.request, "urlopen",
                        lambda *a, **kw: time.sleep(30))
    t0 = time.monotonic()
    assert tpu_mod._gce_metadata("instance/name", timeout=0.1) is None
    assert time.monotonic() - t0 < 2.0


def test_unknown_device_kind_has_no_peak():
    from ray_tpu._private.accelerators.tpu import (
        bf16_peak_flops_per_chip,
        hbm_peak_bytes_per_sec,
    )

    assert bf16_peak_flops_per_chip("TPU v5 lite") == 197e12
    assert hbm_peak_bytes_per_sec("TPU v5 lite") == 819e9
    for kind in ("cpu", "TPU v9", ""):
        with pytest.raises(ValueError, match="no published peak"):
            bf16_peak_flops_per_chip(kind)
        with pytest.raises(ValueError, match="no published peak"):
            hbm_peak_bytes_per_sec(kind)


def test_visible_chips_env():
    from ray_tpu._private.accelerators.tpu import visible_chips_env

    assert visible_chips_env((2,), 4) == {
        "TPU_VISIBLE_CHIPS": "2", "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
        "TPU_HOST_BOUNDS": "1,1,1"}
    assert visible_chips_env((2, 3), 4)["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"
    # the whole host: libtpu's defaults stand
    assert visible_chips_env((0, 1, 2, 3), 4) == {}
    assert visible_chips_env((0,), 1) == {}
    # other sub-host counts: the ids alone, as the reference does
    assert visible_chips_env((0, 1, 2, 3), 8) == {
        "TPU_VISIBLE_CHIPS": "0,1,2,3"}


def test_chip_ids_handed_out_and_returned_with_leases(ray_start_cluster):
    """One process per chip: workers granted `TPU: 1` each see a different
    chip; the id comes back only when the process is gone; and a worker
    that ran with a chip is never pooled."""
    import os
    import time

    raylet = ray_start_cluster.add_node(num_cpus=4, resources={"TPU": 4})
    ray_start_cluster.connect()

    def wait_free(want):
        deadline = time.monotonic() + 10
        while raylet._free_chips != want and time.monotonic() < deadline:
            time.sleep(0.02)
        assert raylet._free_chips == want

    def who():
        return (os.getpid(), os.environ.get("TPU_VISIBLE_CHIPS"),
                os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS"))

    @ray_tpu.remote(resources={"TPU": 1})
    class Holder:
        def who(self):
            return who()

    holders = [Holder.remote() for _ in range(4)]
    seen = ray_tpu.get([h.who.remote() for h in holders])
    assert sorted(chip for _, chip, _ in seen) == ["0", "1", "2", "3"]
    assert {bounds for _, _, bounds in seen} == {"1,1,1"}
    wait_free([])

    ray_tpu.kill(holders[2])
    freed = int(seen[2][1])
    wait_free([freed])

    task = ray_tpu.remote(resources={"TPU": 1})(who)
    pid1, chip1, _ = ray_tpu.get(task.remote())
    assert chip1 == str(freed)
    wait_free([freed])  # released on the worker's death, not before
    pid2, chip2, _ = ray_tpu.get(task.remote())
    assert chip2 == str(freed)
    assert pid2 != pid1, "a worker that held a chip went back to the pool"

    for h in holders:
        ray_tpu.kill(h)
    wait_free([0, 1, 2, 3])
    # the whole host: no confinement; plain workers: held to the CPU
    whole = ray_tpu.remote(resources={"TPU": 4})(who)
    assert ray_tpu.get(whole.remote())[1] is None
    plain = ray_tpu.remote(lambda: os.environ.get("JAX_PLATFORMS"))
    assert ray_tpu.get(plain.remote()) == "cpu"
    wait_free([0, 1, 2, 3])
    ray_tpu.shutdown()


def test_garbled_worker_id_degrades_not_crashes():
    env = _slice_env("slice-a", worker_id=0)
    env["TPU_WORKER_ID"] = "not-a-number"
    info = detect_tpu(env)
    assert info is not None and info.worker_id == 0


# ---------------------------------------------------------------- placement

def test_tpu_gang_lands_on_single_slice(ray_start_cluster):
    """A 2-host TPU gang must pick ONE slice even when its two bundles
    would individually fit on hosts of different slices."""
    cluster = ray_start_cluster
    # two 2-host slices; 1 CPU each so CPU can't dominate packing
    for slice_name in ("slice-a", "slice-b"):
        for wid in (0, 1):
            cluster.add_node(
                num_cpus=1,
                accelerator_env=_slice_env(slice_name, worker_id=wid))
    cluster.wait_for_nodes()
    cluster.connect()

    pg = placement_group([{"TPU": 4}, {"TPU": 4}], strategy="STRICT_PACK")
    ray_tpu.get(pg.ready(), timeout=60)

    table = placement_group_table()[pg.id.hex()]
    node_ids = set(table["bundle_locations"].values())
    assert len(node_ids) == 2  # one host per 4-chip bundle

    # both chosen hosts belong to the same slice
    slices = set()
    for node in ray_tpu.nodes():
        if node["NodeID"] in {n for n in node_ids}:
            slices.add(node["Labels"].get(SLICE_NAME_LABEL))
    assert len(slices) == 1
    remove_placement_group(pg)


def test_tpu_gang_refuses_to_straddle_slices(ray_start_cluster):
    """A gang needing 3 hosts with only 2-host slices available must stay
    PENDING (never straddle), and a feasible 2-host gang still places."""
    cluster = ray_start_cluster
    for slice_name in ("slice-a", "slice-b"):
        for wid in (0, 1):
            cluster.add_node(
                num_cpus=1,
                accelerator_env=_slice_env(slice_name, worker_id=wid))
    cluster.wait_for_nodes()
    cluster.connect()

    pg = placement_group([{"TPU": 4}] * 3, strategy="STRICT_PACK")
    assert pg.wait(timeout_seconds=3) is False
    state = placement_group_table()[pg.id.hex()]["state"]
    assert state in ("PENDING", "RESCHEDULING")
    remove_placement_group(pg)

    pg2 = placement_group([{"TPU": 4}] * 2, strategy="STRICT_PACK")
    ray_tpu.get(pg2.ready(), timeout=60)
    remove_placement_group(pg2)


def test_tpu_gang_reschedules_wholesale_after_host_death(ray_start_cluster):
    """Losing a slice host must re-place the WHOLE gang (never leave the
    surviving bundle on the old slice and push the lost one elsewhere —
    that would straddle ICI domains)."""
    import time

    cluster = ray_start_cluster
    nodes = {}
    for slice_name in ("slice-a", "slice-b"):
        for wid in (0, 1):
            nodes[(slice_name, wid)] = cluster.add_node(
                num_cpus=1,
                accelerator_env=_slice_env(slice_name, worker_id=wid))
    cluster.wait_for_nodes()
    cluster.connect()

    pg = placement_group([{"TPU": 4}, {"TPU": 4}], strategy="STRICT_PACK")
    assert pg.wait(30)
    locs = placement_group_table()[pg.id.hex()]["bundle_locations"]
    labels = {n["NodeID"]: n["Labels"] for n in ray_tpu.nodes()}
    (first_slice,) = {labels[n].get(SLICE_NAME_LABEL) for n in locs.values()}

    # kill one host of the gang's slice (ungraceful: found via heartbeats)
    victim = nodes[(first_slice, 1)]
    victim_id = victim.node_id.hex()
    cluster.kill_node(victim, allow_graceful=False)

    # first wait until the GCS notices the death (the gang is untouched
    # until then, so polling for CREATED immediately would pass vacuously)
    deadline = time.time() + 60
    while time.time() < deadline:
        if not any(n["NodeID"] == victim_id and n["Alive"]
                   for n in ray_tpu.nodes()):
            break
        time.sleep(0.5)
    else:
        raise AssertionError("node death was never detected")

    while time.time() < deadline:
        table = placement_group_table()[pg.id.hex()]
        if (table["state"] == "CREATED"
                and len(table["bundle_locations"]) == 2
                and victim_id not in table["bundle_locations"].values()):
            new_locs = table["bundle_locations"]
            labels = {n["NodeID"]: n["Labels"] for n in ray_tpu.nodes()}
            slices = {labels[n].get(SLICE_NAME_LABEL)
                      for n in new_locs.values()}
            if len(slices) == 1:
                break
        time.sleep(0.5)
    else:
        raise AssertionError(
            f"gang did not recover onto a single slice: {table}")
    # the dead slice has only one live host left, so the gang must have
    # moved wholesale to the other slice
    assert slices == {"slice-b" if first_slice == "slice-a" else "slice-a"}
    remove_placement_group(pg)


def test_tpu_head_resource_schedules_gang_entry(ray_start_cluster):
    """The TPU-<type>-head resource targets worker 0 of a slice — the gang
    entry point a trainer reserves before fanning out over the slice."""
    cluster = ray_start_cluster
    cluster.add_node(num_cpus=1)  # plain CPU node
    for wid in (0, 1):
        cluster.add_node(
            num_cpus=1, accelerator_env=_slice_env("slice-a", worker_id=wid))
    cluster.wait_for_nodes()
    cluster.connect()

    head_res = tpu_head_resource_name("v5litepod-16")
    assert ray_tpu.cluster_resources().get(head_res) == 1.0

    @ray_tpu.remote(resources={head_res: 1}, num_cpus=0)
    def on_slice_head():
        return ray_tpu.get_runtime_context().get_node_id()

    node_id = ray_tpu.get(on_slice_head.remote(), timeout=60)
    labels = {n["NodeID"]: n["Labels"] for n in ray_tpu.nodes()}
    assert labels[node_id].get(SLICE_NAME_LABEL) == "slice-a"
    assert labels[node_id].get("ray.io/tpu-worker-id") == "0"


# ------------------------------------ a chip another process has not let go

def _vfio_root(tmp_path, groups=("0", "1", "2", "3")):
    (tmp_path / "vfio").mkdir()
    for name in (*groups, "vfio"):
        (tmp_path / "vfio" / name).write_bytes(b"")
    return str(tmp_path)


def test_wait_until_chips_free_waits_while_a_group_is_busy(
        tmp_path, monkeypatch):
    import errno
    import os
    import time

    from ray_tpu._private.accelerators import tpu

    root = _vfio_root(tmp_path)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    opened, real_open = [], os.open

    def _open(path, flags, *a, **kw):
        if str(path).startswith(root):
            opened.append(os.path.basename(path))
            if path.endswith("/2") and opened.count("2") <= 3:
                raise OSError(errno.EBUSY, "Device or resource busy")
        return real_open(path, flags, *a, **kw)

    monkeypatch.setattr(os, "open", _open)
    t0 = time.monotonic()
    tpu.wait_until_chips_free(dev_root=root, poll_s=0.01)
    assert opened.count("2") == 4 and 0.03 <= time.monotonic() - t0 < 5.0
    assert "vfio" not in opened   # the container node is no chip
    # confined to one chip: only that chip's group is asked
    del opened[:]
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "1")
    tpu.wait_until_chips_free(dev_root=root, poll_s=0.01)
    assert set(opened) == {"1"}


@pytest.mark.parametrize("case", ["told_cpu", "no_nodes", "not_allowed",
                                  "held_by_me", "never_freed"])
def test_wait_until_chips_free_returns_where_waiting_cannot_help(
        tmp_path, monkeypatch, case):
    import errno
    import os
    import time

    from ray_tpu._private.accelerators import tpu

    root = _vfio_root(tmp_path) if case != "no_nodes" else str(tmp_path)
    monkeypatch.setenv("JAX_PLATFORMS",
                       "cpu" if case == "told_cpu" else "tpu,cpu")
    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    real_open, held = os.open, None
    if case == "held_by_me":
        held = real_open(os.path.join(root, "vfio", "3"), os.O_RDWR)
    code = errno.EACCES if case == "not_allowed" else errno.EBUSY

    def _open(path, flags, *a, **kw):
        if str(path).startswith(root):
            raise OSError(code, os.strerror(code))
        return real_open(path, flags, *a, **kw)

    monkeypatch.setattr(os, "open", _open)
    # (a wait that cannot help would last its whole 2 s: the limit below
    # leaves a loaded host a second to return "at once")
    timeout_s = 0.2 if case == "never_freed" else 2.0
    t0 = time.monotonic()
    try:
        tpu.wait_until_chips_free(timeout_s=timeout_s, dev_root=root,
                                  poll_s=0.01)
    finally:
        if held is not None:
            os.close(held)
    if case == "never_freed":   # gives up at the timeout, raises nothing
        assert 0.2 <= time.monotonic() - t0 < 2.0
    else:
        assert time.monotonic() - t0 < 1.0


class _Proc:
    """A worker's process as `WorkerPool.shutdown` sees it: gone from
    `poll()` at once or after the kill, reaped `reap_s` after that."""

    def __init__(self, reap_s: float):
        self.reap_s, self.calls = reap_s, []
        self._t0 = time.monotonic()

    def poll(self):
        return None

    def terminate(self):
        self.calls.append("terminate")

    def kill(self):
        self.calls.append("kill")

    def wait(self, timeout=None):
        self.calls.append("wait")
        left = self._t0 + self.reap_s - time.monotonic()
        if left > (timeout or 0):
            time.sleep(timeout or 0)
            raise TimeoutError("still there")
        time.sleep(max(0.0, left))
        return -9


@pytest.mark.parametrize("case", ["reaped_in_time", "outlives_the_wait"])
def test_pool_shutdown_waits_for_workers_that_held_chips_and_no_longer(
        monkeypatch, case):
    """`ray_tpu.shutdown()` returns with the chips free: a worker started
    for chips is waited for until it is reaped, within ONE bound for all of
    them; a worker without chips gets the 2 s it always got and a kill."""
    from ray_tpu.raylet import worker_pool

    monkeypatch.setattr(worker_pool, "_CHIP_RELEASE_WAIT_S", 1.5)
    slow = 0.3 if case == "reaped_in_time" else 30.0
    chips = [worker_pool.WorkerHandle(
        pid=100 + i, proc=_Proc(2.0 + slow), needs_accelerator=True,
        chip_ids=(i,)) for i in range(2)]
    plain = worker_pool.WorkerHandle(pid=7, proc=_Proc(30.0))
    pool = worker_pool.WorkerPool.__new__(worker_pool.WorkerPool)
    pool._closed, pool._monitor_task, pool._zygote = False, None, None
    pool._workers = {h.pid: h for h in (*chips, plain)}
    t0 = time.monotonic()
    pool.shutdown()
    took = time.monotonic() - t0
    # 2 s for everyone's terminate, then the chips' bound, once (the upper
    # limits leave a loaded host a second: tier-1 runs six files at a time)
    if case == "reaped_in_time":
        assert 2.2 <= took < 3.4   # reaped at 2.3 s: before the bound ends
    else:
        assert 3.4 <= took < 4.8   # not 1.5 s a worker (5.0), not 30 s
    for h in chips:
        assert h.proc.calls[:2] == ["terminate", "wait"]
        assert h.proc.calls.count("wait") == 2 and "kill" in h.proc.calls
    # killed after its 2 s, and not waited for again
    assert plain.proc.calls == ["terminate", "wait", "kill"]
