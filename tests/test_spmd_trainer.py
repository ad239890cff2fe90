"""Multi-device SPMD training-path tests (ISSUE 7).

Run on the 8-virtual-device CPU mesh the whole suite fakes
(conftest sets --xla_force_host_platform_device_count=8): the n-device
pjit step over the named (dp, fsdp, tp) mesh must be a pure
re-partitioning of the 1-device program — same losses, canonical
per-parameter PartitionSpecs, sharded optimizer state, mesh-matching data
ingest — and a chaos-killed gang must re-establish the same mesh from a
checkpoint and resume with identical losses.

`pytest -m spmd` is the fast gate for mesh/sharding/collective changes
(CONTRIBUTING: mesh-touching PRs must run it).
"""

import dataclasses
import json
import os
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ray_tpu
from ray_tpu import train
from ray_tpu.air import FailureConfig, RunConfig, ScalingConfig
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.parallel.sharding import LogicalAxisRules, logical_sharding
from ray_tpu.train import Checkpoint, JaxConfig, JaxTrainer
from ray_tpu.util import collective as col

pytestmark = pytest.mark.spmd

# float32 accumulation order differs between the 1-device and partitioned
# programs (reductions re-associate across shards); observed divergence on
# the tiny model is <1e-6 per step — 1e-4 leaves margin without letting a
# semantic difference (wrong masking, wrong reduction axis) through.
LOSS_ATOL = 1e-4

MESH_PLAN = {"dp": 2, "fsdp": 2, "tp": 2}


def _tiny_cfg():
    from ray_tpu.models import llama

    return dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)


def _make_state_and_step(mesh, cfg, steps_batch=None):
    import optax

    from ray_tpu.models import llama
    from ray_tpu.train.step import init_train_state, make_train_step

    rules = LogicalAxisRules()
    opt = optax.adamw(1e-3)
    state, shardings = init_train_state(
        partial(llama.init, cfg), opt, llama.param_logical_axes(cfg),
        mesh, jax.random.PRNGKey(0), rules)
    bs = logical_sharding(mesh, ("batch", "seq"), rules)
    step = make_train_step(
        partial(llama.loss_fn, config=cfg, mesh=mesh, rules=rules),
        opt, shardings, batch_sharding={"inputs": bs, "targets": bs})
    return state, shardings, step, bs


def _token_batch(cfg, batch, seq, key=1):
    return jax.random.randint(
        jax.random.PRNGKey(key), (batch, seq + 1), 0, cfg.vocab_size)


# -- (a) n-device step == 1-device step on the same global batch -----------


def test_ndev_step_matches_1dev_loss():
    assert len(jax.devices()) >= 8, "conftest must fake 8 devices"
    cfg = _tiny_cfg()
    batch, seq, steps = 8, 128, 3
    toks = _token_batch(cfg, batch, seq)

    def run(mesh):
        state, _, step, bs = _make_state_and_step(mesh, cfg)
        b = {"inputs": jax.device_put(toks[:, :-1], bs),
             "targets": jax.device_put(toks[:, 1:], bs)}
        losses = []
        for _ in range(steps):
            state, m = step(state, b)
            losses.append(float(m["loss"]))
        return losses

    losses_1 = run(build_mesh(MeshConfig(), devices=jax.devices()[:1]))
    losses_n = run(build_mesh(MeshConfig(**MESH_PLAN)))
    np.testing.assert_allclose(losses_n, losses_1, atol=LOSS_ATOL, rtol=0)
    assert losses_n[-1] < losses_n[0], "loss must decrease"


# -- (b) parameter / optimizer shards carry the canonical PartitionSpecs ---


def test_param_and_opt_state_partition_specs():
    from jax.sharding import PartitionSpec as P

    cfg = _tiny_cfg()
    mesh = build_mesh(MeshConfig(**MESH_PLAN))
    state, shardings, _, _ = _make_state_and_step(mesh, cfg)

    # canonical rules: embed-dim over fsdp, heads/mlp/vocab over tp
    expected = {
        "embed": P("tp", "fsdp"),        # [vocab, embed]
        "lm_head": P("fsdp", "tp"),      # [embed, vocab]
    }
    for name, spec in expected.items():
        assert state.params[name].sharding.spec == spec, (
            name, state.params[name].sharding.spec)
    layers = state.params["layers"]
    # stacked layer dim replicated; embed over fsdp; heads/mlp over tp
    assert layers["wq"].sharding.spec == P(None, "fsdp", "tp", None)
    assert layers["w_up"].sharding.spec == P(None, "fsdp", "tp")
    assert layers["attn_norm"].sharding.spec == P(None, None)

    # ZeRO-style optimizer state: mu/nu shard exactly like their params
    import optax

    adam_state = state.opt_state[0]
    assert isinstance(adam_state, optax.ScaleByAdamState)
    for moment in (adam_state.mu, adam_state.nu):
        jax.tree.map(
            lambda m, p: (m.sharding, p.sharding),
            moment, state.params)  # structure match
        pairs = zip(jax.tree.leaves(moment), jax.tree.leaves(state.params))
        assert all(m.sharding == p.sharding for m, p in pairs)
    # scalar step counters replicated
    assert adam_state.count.sharding.spec == P()
    assert state.step.sharding.spec == P()


# -- (c) iter_jax_batches output shardings match the trainer mesh ----------


def test_iter_jax_batches_matches_trainer_mesh(ray_start_regular):
    import ray_tpu.data as rt_data

    mesh = build_mesh(MeshConfig(**MESH_PLAN))
    bs = train.batch_sharding(mesh=mesh)
    items = [{"x": np.full((16,), i, np.float32),
              "y": np.arange(4, dtype=np.int32) + i} for i in range(8)]
    ds = rt_data.from_items(items)
    got = list(ds.iter_jax_batches(batch_size=8, sharding=bs))
    assert len(got) == 1
    for key in ("x", "y"):
        arr = got[0][key]
        assert arr.sharding == bs, (key, arr.sharding)
        # batch dim split over dp*fsdp=4: each device holds 2 rows — the
        # full batch is never replicated onto a device
        assert len(arr.addressable_shards) == 8
        assert all(s.data.shape[0] == 2 for s in arr.addressable_shards)
    ref = np.stack([it["x"] for it in items])
    np.testing.assert_array_equal(np.asarray(got[0]["x"]), ref)


# -- mesh collective backend: in-jit lowering + typed misuse ---------------


def _mesh_group(name, mesh_axes=("dp",)):
    col.init_collective_group(1, 0, backend="mesh", group_name=name,
                              mesh_axes=mesh_axes)


def test_mesh_collective_lowers_in_jit():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    _mesh_group("m_lower")
    try:
        mesh = col.bootstrap_mesh(MeshConfig(dp=8), group_name="m_lower")
        x = jnp.arange(8.0)

        f = jax.jit(shard_map(
            lambda v: col.allreduce(v, group_name="m_lower"),
            mesh=mesh, in_specs=P("dp"), out_specs=P()))
        assert float(f(x)[0]) == float(np.sum(np.arange(8.0)))

        g = jax.jit(shard_map(
            lambda v: col.allgather(v, group_name="m_lower"),
            mesh=mesh, in_specs=P("dp"), out_specs=P("dp", None)))
        assert g(x).shape == (64, 1)

        b = jax.jit(shard_map(
            lambda v: col.broadcast(v, src_rank=5, group_name="m_lower"),
            mesh=mesh, in_specs=P("dp"), out_specs=P()))
        assert float(b(x)[0]) == 5.0

        rs = jax.jit(shard_map(
            lambda v: col.reducescatter(v, group_name="m_lower"),
            mesh=mesh, in_specs=P(None, "dp"), out_specs=P("dp")))
        out = rs(jnp.ones((8, 8)))
        np.testing.assert_array_equal(np.asarray(out), np.full((8,), 8.0))

        # pytree chunk lists stack leaf-wise (the host path's contract)
        rs_tree = jax.jit(shard_map(
            lambda v: col.reducescatter(
                [{"g": v[i]} for i in range(8)], group_name="m_lower"),
            mesh=mesh, in_specs=P(None, "dp"), out_specs=P("dp")))
        out = rs_tree(jnp.ones((8, 8)))
        np.testing.assert_array_equal(np.asarray(out["g"]),
                                      np.full((8,), 8.0))

        # a mis-sized chunk list is the typed error, not an XLA shape error
        with pytest.raises(col.MeshCollectiveError, match="one chunk per"):
            jax.jit(shard_map(
                lambda v: col.reducescatter(
                    [v[i] for i in range(3)], group_name="m_lower"),
                mesh=mesh, in_specs=P(None, "dp"), out_specs=P("dp")))(
                    jnp.ones((8, 8)))

        # an out-of-range in-jit broadcast source would match no device
        # position (masked psum → silent zeros): typed error instead
        with pytest.raises(col.MeshCollectiveError, match="out of range"):
            jax.jit(shard_map(
                lambda v: col.broadcast(v, src_rank=8,
                                        group_name="m_lower"),
                mesh=mesh, in_specs=P("dp"), out_specs=P()))(jnp.ones(8))

        # both guards must also fire on a mesh_axes-only group (no
        # bootstrap_mesh → g.mesh is None): the axis size comes from the
        # bound axis environment at trace time
        _mesh_group("m_axes")
        try:
            with pytest.raises(col.MeshCollectiveError,
                               match="out of range"):
                jax.jit(shard_map(
                    lambda v: col.broadcast(v, src_rank=8,
                                            group_name="m_axes"),
                    mesh=mesh, in_specs=P("dp"), out_specs=P()))(
                        jnp.ones(8))
            with pytest.raises(col.MeshCollectiveError,
                               match="one chunk per"):
                jax.jit(shard_map(
                    lambda v: col.reducescatter(
                        [v[i] for i in range(3)], group_name="m_axes"),
                    mesh=mesh, in_specs=P(None, "dp"),
                    out_specs=P("dp")))(jnp.ones((8, 8)))
        finally:
            col.destroy_collective_group("m_axes")
    finally:
        col.destroy_collective_group("m_lower")


def test_mesh_collective_misuse_is_typed():
    """A traced value with no mesh axes bound must raise the typed
    MeshCollectiveError (not a bare NameError/assert) with an actionable
    message."""
    _mesh_group("m_misuse")
    try:
        with pytest.raises(col.MeshCollectiveError) as ei:
            jax.jit(lambda v: col.allreduce(v, group_name="m_misuse"))(
                jnp.ones(4))
        msg = str(ei.value)
        assert "shard_map" in msg and "mesh" in msg
        # in-jit p2p has no lowering: typed, names the alternative
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = build_mesh(MeshConfig(dp=8))
        with pytest.raises(col.MeshCollectiveError, match="ppermute"):
            jax.jit(shard_map(
                lambda v: col.send(v, 1, group_name="m_misuse"),
                mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(
                    jnp.ones(8))
    finally:
        col.destroy_collective_group("m_misuse")


def test_mesh_collective_degenerate_1device_mesh_is_identity():
    """The laptop-to-pod code path must degrade gracefully: on a 1-device
    (all-size-1) mesh, bootstrap_mesh + an in-jit collective is identity,
    not a MeshCollectiveError."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    col.init_collective_group(1, 0, backend="mesh", group_name="m_one")
    try:
        mesh = col.bootstrap_mesh(MeshConfig(), group_name="m_one",
                                  devices=jax.devices()[:1])
        assert all(s == 1 for s in mesh.shape.values())
        f = jax.jit(shard_map(
            lambda v: col.allreduce(v, group_name="m_one"),
            mesh=mesh, in_specs=P(), out_specs=P()))
        np.testing.assert_array_equal(np.asarray(f(jnp.arange(4.0))),
                                      np.arange(4.0))
    finally:
        col.destroy_collective_group("m_one")


def test_mesh_group_host_values_use_host_path():
    """Out-of-jit metadata on a mesh group rides the host path — world-1
    groups never touch the actor plane (usable without a cluster)."""
    _mesh_group("m_host")
    try:
        out = col.allreduce(np.array([3.0]), group_name="m_host")
        assert float(out[0]) == 3.0
        assert col.allgather({"r": np.array([1])},
                             group_name="m_host")[0]["r"][0] == 1
        assert col.get_group_info("m_host")["world_size"] == 1
    finally:
        col.destroy_collective_group("m_host")


# -- (d) chaos-killed gang worker: restart re-establishes mesh + loss ------


TOTAL_STEPS = 8


def _make_spmd_train_fn():
    """A mesh-native train_fn shipped BY VALUE: gang workers cannot import
    this test module, so the fn is a NESTED def (dynamic =
    cloudpickle-by-value) referencing no test-module global — only its own
    imports. It restores the sharded TrainState from the latest checkpoint
    and continues: a restarted gang must reproduce the uninterrupted loss
    trajectory exactly."""

    def _spmd_train_fn(config):
        import dataclasses
        from functools import partial as _partial

        import jax
        import jax.numpy as jnp
        import optax

        from ray_tpu import train as rt_train
        from ray_tpu.models import llama
        from ray_tpu.parallel.sharding import LogicalAxisRules, logical_sharding
        from ray_tpu.train.checkpoint import Checkpoint as Ckpt
        from ray_tpu.train.step import (
            TrainState,
            _as_dict,
            init_train_state,
            make_train_step,
        )

        mesh = rt_train.get_mesh()
        assert mesh is not None, "mesh-native mode must provide the gang mesh"
        cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
        rules = LogicalAxisRules()
        opt = optax.adamw(1e-3)
        state, shardings = init_train_state(
            _partial(llama.init, cfg), opt, llama.param_logical_axes(cfg),
            mesh, jax.random.PRNGKey(0), rules)
        bs = logical_sharding(mesh, ("batch", "seq"), rules)
        step = make_train_step(
            _partial(llama.loss_fn, config=cfg, mesh=mesh, rules=rules),
            opt, shardings, batch_sharding={"inputs": bs, "targets": bs})

        start = 0
        ckpt = rt_train.get_checkpoint()
        if ckpt is not None:
            host = ckpt.to_arrays()
            start = int(host["step"])
            # re-place the host checkpoint into the re-established mesh's
            # shardings (device_put against the spec tree)
            placed = jax.tree.map(jax.device_put, host["state"],
                                  _as_dict(shardings))
            state = TrainState(**placed)
        for i in range(start, config["total_steps"]):
            toks = jax.random.randint(
                jax.random.PRNGKey(100 + i), (8, 129), 0, cfg.vocab_size)
            b = {"inputs": jax.device_put(toks[:, :-1], bs),
                 "targets": jax.device_put(toks[:, 1:], bs)}
            state, m = step(state, b)
            ck = Ckpt.from_arrays({
                "state": jax.device_get(
                    {"params": state.params, "opt_state": state.opt_state,
                     "step": state.step}),
                "step": i + 1,
            })
            rt_train.report(
                {"loss": float(m["loss"]), "step": i,
                 "mesh_axes": {k: int(v) for k, v in mesh.shape.items()}},
                checkpoint=ck)


    return _spmd_train_fn

@pytest.mark.slow
@pytest.mark.thread_leak_ok  # chaos env plan armed for spawned workers
def test_gang_restart_from_checkpoint_after_chaos_kill(tmp_path,
                                                       monkeypatch):
    """A chaos rule kills the gang worker's process mid-run (env-armed,
    counted at the actor-push chokepoint like test_event_log's kill
    scenario); the trainer restarts the gang, the worker re-establishes
    the SAME mesh, restores the sharded state from the latest checkpoint,
    and the merged loss trajectory is IDENTICAL (atol=LOSS_ATOL) to an
    uninterrupted in-process run of the same program."""
    from ray_tpu import chaos

    # Worker push budget: ~6 setup pushes (get_metadata, jax init, mesh
    # bootstrap, group_metadata, init_session, start_training) before the
    # first next_result. after=12 kills the first incarnation on its 13th
    # push = 7th next_result (≥6 checkpoints persisted); the restarted
    # incarnation resumes near step 6 and finishes in ~10 pushes, safely
    # under the re-armed counter.
    plan = chaos.ChaosPlan(seed=7, rules=[
        chaos.ChaosRule(action="kill", site="before_execute",
                        method="push_task_w", label="worker",
                        after=12, times=1),
    ]).to_json()
    monkeypatch.setenv(chaos.ENV_VAR, plan)
    ray_tpu.init(num_cpus=2)
    try:
        trainer = JaxTrainer(
            _make_spmd_train_fn(),
            train_loop_config={"total_steps": TOTAL_STEPS},
            jax_config=JaxConfig(distributed=False, platform="cpu",
                                 mesh_config=MeshConfig(**MESH_PLAN)),
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(
                name="spmd_chaos", storage_path=str(tmp_path / "results"),
                failure_config=FailureConfig(max_failures=2)),
        )
        result = trainer.fit()
        assert result.error is None, f"fit failed: {result.error}"
        assert result.metrics["step"] == TOTAL_STEPS - 1
        assert result.metrics["mesh_axes"]["dp"] == MESH_PLAN["dp"]
        assert result.metrics["mesh_axes"]["fsdp"] == MESH_PLAN["fsdp"]
        assert result.metrics["mesh_axes"]["tp"] == MESH_PLAN["tp"]

        # the reported rows: every step 0..7 present; steps re-reported
        # after the restart must agree with the pre-kill report
        rows = [json.loads(line) for line in
                open(os.path.join(result.path, "result.json"))]
        by_step = {}
        killed_and_resumed = False
        for r in rows:
            if r["step"] in by_step:
                killed_and_resumed = True
                assert abs(by_step[r["step"]] - r["loss"]) <= LOSS_ATOL
            by_step[r["step"]] = r["loss"]
        assert sorted(by_step) == list(range(TOTAL_STEPS))

        # identical to the uninterrupted program, run in-process on the
        # same 8-device mesh
        cfg = _tiny_cfg()
        mesh = build_mesh(MeshConfig(**MESH_PLAN))
        state, _, step, bs = _make_state_and_step(mesh, cfg)
        for i in range(TOTAL_STEPS):
            toks = _token_batch(cfg, 8, 128, key=100 + i)
            b = {"inputs": jax.device_put(toks[:, :-1], bs),
                 "targets": jax.device_put(toks[:, 1:], bs)}
            state, m = step(state, b)
            assert abs(float(m["loss"]) - by_step[i]) <= LOSS_ATOL, (
                f"step {i}: {float(m['loss'])} vs {by_step[i]}")
        assert killed_and_resumed or len(rows) == TOTAL_STEPS
    finally:
        chaos.uninstall()
        ray_tpu.shutdown()


@pytest.mark.slow
def test_mesh_gang_two_process_global_mesh(ray_start_regular, tmp_path):
    """Mesh-native distributed gang: 2 worker processes x 4 faked local
    devices rendezvous through the collective group (bootstrap_mesh feeds
    jax.distributed.initialize) and agree on ONE 8-device global mesh —
    the same code path a single-process mesh takes, minus nothing."""

    def train_fn(config):
        import jax

        from ray_tpu import train as rt_train

        mesh = rt_train.get_mesh()
        assert mesh is not None
        assert jax.process_count() == 2
        assert jax.device_count() == 8
        assert dict(mesh.shape)["dp"] == 8
        assert len(mesh.devices.reshape(-1)) == 8
        rt_train.report({"devices": jax.device_count(),
                         "processes": jax.process_count()})

    trainer = JaxTrainer(
        train_fn,
        jax_config=JaxConfig(
            distributed=True, platform="cpu",
            mesh_config=MeshConfig(dp=8),
            env_vars={"XLA_FLAGS":
                      "--xla_force_host_platform_device_count=4"}),
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="spmd_dist",
                             storage_path=str(tmp_path / "results")),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["devices"] == 8
    assert result.metrics["processes"] == 2


def test_jax_trainer_mesh_config_composes_with_backend_config():
    """mesh_config must survive an explicit backend_config= kwarg (the
    documented DataParallelTrainer spelling) — silently dropping it would
    start the gang in legacy non-mesh mode."""
    mc = MeshConfig(**MESH_PLAN)
    t = JaxTrainer(lambda c: None, backend_config=JaxConfig(platform="cpu"),
                   mesh_config=mc)
    assert t.backend_config.mesh_config is mc
    assert t.backend_config.platform == "cpu"
    t2 = JaxTrainer(lambda c: None, jax_config=JaxConfig(), mesh_config=mc)
    assert t2.backend_config.mesh_config is mc
    with pytest.raises(ValueError, match="not both"):
        JaxTrainer(lambda c: None, jax_config=JaxConfig(),
                   backend_config=JaxConfig())


def test_mesh_mode_multiworker_requires_distributed():
    """distributed=False with a multi-worker mesh gang would silently build
    N identical-shaped independent local meshes (no gradient sync at all);
    the backend must refuse up front instead."""
    from ray_tpu.train.backend import JaxBackend, JaxConfig

    class _Gang:
        num_workers = 2

    cfg = JaxConfig(distributed=False, mesh_config=MeshConfig(dp=2))
    with pytest.raises(ValueError, match="distributed=True"):
        JaxBackend().on_start(_Gang(), cfg)


# -- ScalingConfig -> slice placement --------------------------------------


def test_scaling_config_topology_slice_mapping():
    sc = ScalingConfig(num_workers=4, topology="v5e-8")
    # topology gangs are STRICT_PACK (one ICI domain) by default
    assert sc.placement_strategy == "STRICT_PACK"
    bundles = sc.worker_bundles()
    assert len(bundles) == 4
    # per-worker chips + the typed slice resource on every bundle
    for b in bundles:
        assert b["TPU"] == 8.0  # v5e-8: single-host slice, 8 chips
        assert b["TPU-v5e-8"] == 8.0
    # the gang resource rides bundle 0 only
    assert bundles[0]["TPU-v5e-8-head"] == 1.0
    assert all("TPU-v5e-8-head" not in b for b in bundles[1:])
    # explicit strategy wins
    sc2 = ScalingConfig(num_workers=2, topology="v5e-8",
                        placement_strategy="SPREAD")
    assert sc2.placement_strategy == "SPREAD"


def test_chips_per_host_honors_env_bounds(monkeypatch):
    # The per-worker TPU demand must match what apply_tpu_detection
    # advertises: with TPU_CHIPS_PER_HOST_BOUNDS set (e.g. GKE single-chip
    # v5e hosts), chips_per_host must honor it via os.environ by default —
    # a generation-default demand of 4 against an advertised 1 would make
    # the topology gang permanently unplaceable.
    from ray_tpu._private.accelerators import chips_per_host

    assert chips_per_host("v5litepod-4") == 4  # generation default
    monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "1,1,1")
    assert chips_per_host("v5litepod-4") == 1
    # explicit env mapping still wins over os.environ
    assert chips_per_host("v5litepod-4", env={}) == 4


def test_tpu_detection_advertises_typed_resource():
    from ray_tpu._private.accelerators import apply_tpu_detection

    env = {"TPU_ACCELERATOR_TYPE": "v5e-8", "TPU_WORKER_ID": "0",
           "TPU_NAME": "slice-a"}
    resources, labels = {}, {}
    info = apply_tpu_detection(resources, labels, env=env)
    assert info is not None
    assert resources["TPU"] == 8.0
    assert resources["TPU-v5e-8"] == 8.0
    assert resources["TPU-v5e-8-head"] == 1.0
