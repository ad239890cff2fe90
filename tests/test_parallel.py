"""Parallel-layer tests on the 8-device CPU mesh (SURVEY §4.4 pattern)."""

import dataclasses
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.parallel.mesh import MeshConfig, axis_plan, build_mesh
from ray_tpu.parallel.sharding import LogicalAxisRules, logical_sharding
from ray_tpu.parallel.ring_attention import ring_attention_sharded
from ray_tpu.parallel.pipeline import pipeline_sharded
from ray_tpu.parallel.moe import moe_layer, moe_shard_map


def _ref_attention(q, k, v, causal):
    d = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        s = q.shape[1]
        mask = np.tril(np.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def test_mesh_config_resolution():
    cfg = MeshConfig(dp=-1, tp=2).resolved(8)
    assert cfg.dp == 4
    with pytest.raises(ValueError):
        MeshConfig(dp=3, tp=3).resolved(8)


@pytest.mark.parametrize("n,plan", [
    (1, {"dp": 1, "fsdp": 1, "tp": 1}),
    (2, {"dp": 1, "fsdp": 1, "tp": 2}),
    (4, {"dp": 1, "fsdp": 2, "tp": 2}),
    (8, {"dp": 2, "fsdp": 2, "tp": 2}),
    (3, {"dp": 3, "fsdp": 1, "tp": 1}),
])
def test_axis_plan_fills_model_axes_first(n, plan):
    """What chip_smoke.py's train phase and the four-chip cell's mesh
    (fsdp 2 x tp 2) rest on: tp, then fsdp, take a factor of two each
    while the count allows; the rest is data parallel."""
    assert axis_plan(n) == plan
    assert MeshConfig(**plan).resolved(n).dp == plan["dp"]


def test_build_mesh_axes():
    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    assert dict(mesh.shape) == {
        "pp": 1, "dp": 2, "fsdp": 2, "ep": 1, "sp": 1, "tp": 2
    }


def test_logical_sharding_drops_size1_axes():
    mesh = build_mesh(MeshConfig(dp=8))
    rules = LogicalAxisRules()
    spec = rules.to_physical(("batch", "seq", "act_heads"), mesh)
    # tp and sp have size 1 -> dropped; batch keeps dp only.
    assert spec[0] == "dp"
    assert spec[1] is None and spec[2] is None


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    mesh = build_mesh(MeshConfig(dp=1, sp=8))
    B, S, H, D = 2, 64, 4, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in keys)
    out = ring_attention_sharded(q, k, v, mesh, causal=causal)
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_pipeline_matches_sequential():
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    n_stages, m, mb, d = 4, 8, 4, 16
    key = jax.random.PRNGKey(0)
    ws = jax.random.normal(key, (n_stages, d, d)) * 0.3

    def stage_fn(w, x):
        # Stage params arrive with their local leading stage dim intact
        # (a stage may own several stacked layers); here it's one layer.
        return jnp.tanh(x @ w[0])

    xs = jax.random.normal(jax.random.PRNGKey(1), (m, mb, d))
    piped = pipeline_sharded(stage_fn, mesh)(ws, xs)

    ref = xs
    for i in range(n_stages):
        ref = jax.vmap(lambda x, i=i: jnp.tanh(x @ ws[i]))(ref)
    np.testing.assert_allclose(np.asarray(piped), np.asarray(ref), atol=1e-5)


def _swiglu_experts(e, d, f):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    return {"w_gate": jax.random.normal(ks[0], (e, d, f)) * 0.3,
            "w_up": jax.random.normal(ks[1], (e, d, f)) * 0.3,
            "w_down": jax.random.normal(ks[2], (e, f, d)) * 0.3}


def _swiglu(p, rows):
    return (jax.nn.silu(rows @ p["w_gate"]) * (rows @ p["w_up"])) @ p["w_down"]


def test_moe_layer_routes_and_balances():
    T, D, E = 64, 16, 4
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (T, D))
    gate_w = jax.random.normal(jax.random.PRNGKey(1), (D, E))
    experts = _swiglu_experts(E, D, 8)

    out, aux = moe_layer(x, gate_w, experts, k=2)
    assert out.shape == (T, D)
    assert float(aux.load_balance) > 0 and float(aux.router_z) > 0
    # dropless: every token's two experts, weighted by the router
    probs = jax.nn.softmax(x @ gate_w, axis=-1)
    every = jnp.stack([_swiglu(jax.tree.map(lambda a, i=i: a[i], experts), x)
                       for i in range(E)], axis=1)          # [T, E, D]
    w, idx = jax.lax.top_k(probs, 2)
    want = jnp.einsum("tkd,tk->td",
                      jnp.take_along_axis(every, idx[..., None], 1), w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_moe_shard_map_matches_dense():
    mesh = build_mesh(MeshConfig(dp=2, ep=4))
    T, D, E = 64, 16, 4
    x = jax.random.normal(jax.random.PRNGKey(0), (T, D))
    gate_w = jax.random.normal(jax.random.PRNGKey(1), (D, E))
    experts = _swiglu_experts(E, D, 8)

    dense_out, dense_aux = moe_layer(x, gate_w, experts, k=1)
    sharded_out, sharded_aux = moe_shard_map(
        x, gate_w, _swiglu, experts, mesh, k=1, capacity_factor=4.0
    )
    np.testing.assert_allclose(
        np.asarray(sharded_out), np.asarray(dense_out), atol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(sharded_aux.experts),
                                  np.asarray(dense_aux.experts))
    # The loss terms are statistics of every shard's tokens, so they are
    # the single-program values, not one shard's.
    np.testing.assert_allclose(float(sharded_aux.load_balance),
                               float(dense_aux.load_balance), rtol=1e-5)
    np.testing.assert_allclose(float(sharded_aux.router_z),
                               float(dense_aux.router_z), rtol=1e-5)


def test_llama_tiny_trains_on_tp_fsdp_mesh():
    import optax
    from ray_tpu.models import llama
    from ray_tpu.train.step import init_train_state, make_train_step

    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    cfg = llama.LlamaConfig.tiny()
    rules = LogicalAxisRules()
    opt = optax.adamw(1e-3)
    state, shardings = init_train_state(
        partial(llama.init, cfg), opt, llama.param_logical_axes(cfg),
        mesh, jax.random.PRNGKey(0), rules,
    )
    bs = logical_sharding(mesh, ("batch", "seq"), rules)
    step = make_train_step(
        partial(llama.loss_fn, config=cfg, mesh=mesh, rules=rules),
        opt, shardings, batch_sharding={"inputs": bs, "targets": bs},
    )
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 65), 0, cfg.vocab_size)
    batch = {
        "inputs": jax.device_put(toks[:, :-1], bs),
        "targets": jax.device_put(toks[:, 1:], bs),
    }
    losses = []
    for _ in range(4):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def _train_step_for(mesh_cfg: MeshConfig):
    import optax
    from ray_tpu.models import llama
    from ray_tpu.train.step import init_train_state, make_train_step

    mesh = build_mesh(mesh_cfg)
    cfg = llama.LlamaConfig.tiny()
    rules = LogicalAxisRules()
    opt = optax.adamw(1e-3)
    state, shardings = init_train_state(
        partial(llama.init, cfg), opt, llama.param_logical_axes(cfg),
        mesh, jax.random.PRNGKey(0), rules,
    )
    bs = logical_sharding(mesh, ("batch", "seq"), rules)
    step = make_train_step(
        partial(llama.loss_fn, config=cfg, mesh=mesh, rules=rules),
        opt, shardings, batch_sharding={"inputs": bs, "targets": bs},
    )
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                              cfg.vocab_size)
    batch = {
        "inputs": jax.device_put(toks[:, :-1], bs),
        "targets": jax.device_put(toks[:, 1:], bs),
    }
    return step, state, batch, cfg


def test_collective_report_per_mesh_config():
    """Compiled-HLO collective accounting (VERDICT r3 weak #8): each mesh
    config's train step has the collective SIGNATURE its sharding
    implies, with nonzero bytes — a regression here means XLA started
    moving different traffic for the same mesh."""
    from ray_tpu.models import llama
    from ray_tpu.parallel.hlo_report import collective_report

    # pure DP: gradients all-reduce; traffic on the order of the params
    step, state, batch, cfg = _train_step_for(MeshConfig(dp=8))
    dp = collective_report(step, state, batch)
    assert dp["all-reduce"]["count"] >= 1
    assert dp["all-reduce"]["bytes"] >= cfg.num_params()  # >=1 byte/param
    assert dp["all-gather"]["count"] == 0  # nothing is sharded to gather

    # FSDP: parameters shard; the step must all-gather params and
    # reduce-scatter gradients (or use reduce+gather pairs)
    step, state, batch, _ = _train_step_for(MeshConfig(fsdp=8))
    fsdp = collective_report(step, state, batch)
    assert fsdp["all-gather"]["count"] >= 1
    assert (fsdp["reduce-scatter"]["count"] >= 1
            or fsdp["all-reduce"]["count"] >= 1)
    assert fsdp["all-gather"]["bytes"] > 0

    # TP: activation reductions appear; gradient sync still present
    step, state, batch, _ = _train_step_for(MeshConfig(dp=4, tp=2))
    tp = collective_report(step, state, batch)
    assert tp["total"]["count"] >= 2
    assert tp["total"]["bytes"] > 0


def _llama_loss_and_grads(cfg, toks, mesh_cfg=None, rules=None):
    """Loss and gradients of `llama.loss_fn` on one seeded batch: on a
    single device, or with parameters and batch placed on `mesh_cfg`'s
    mesh (over the first devices it needs)."""
    from ray_tpu.models import llama
    from ray_tpu.parallel.sharding import shard_params

    params = llama.init(cfg, jax.random.PRNGKey(0))
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    if mesh_cfg is None:
        fn = jax.jit(jax.value_and_grad(partial(llama.loss_fn, config=cfg)))
        return fn, params, batch
    n = int(np.prod(list(mesh_cfg.axis_sizes().values())))
    mesh = build_mesh(mesh_cfg, devices=jax.devices()[:n])
    rules = rules or LogicalAxisRules()
    params = shard_params(params, llama.param_logical_axes(cfg), mesh, rules)
    bs = logical_sharding(mesh, ("batch", "seq"), rules,
                          batch["inputs"].shape)
    batch = jax.device_put(batch, bs)
    fn = jax.jit(jax.value_and_grad(
        partial(llama.loss_fn, config=cfg, mesh=mesh, rules=rules)))
    return fn, params, batch


def _seq_sharded_boundaries():
    from ray_tpu._private import device_profiler

    return device_profiler.snapshot()["counters"].get(
        "tp.seq_sharded_boundaries", 0)


def _assert_matches_single_device(cfg, toks, mesh_cfg):
    fn, params, batch = _llama_loss_and_grads(cfg, toks)
    want_loss, want = fn(params, batch)
    fn, params, batch = _llama_loss_and_grads(cfg, toks, mesh_cfg)
    loss, grads = fn(params, batch)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("mesh_cfg,ring", [
    (MeshConfig(dp=1, fsdp=2, tp=2), False),
    (MeshConfig(dp=2, sp=2, tp=2), True),
    (MeshConfig(dp=2, tp=4), False),
], ids=["fsdp2_tp2", "dp2_sp2_tp2", "dp2_tp4"])
def test_llama_seq_sharded_residual_matches_single_device(mesh_cfg, ring):
    """The residual stream between sublayers is sequence-sharded over tp
    (and sp): the loss and EVERY gradient leaf are the single-device
    program's, and the boundaries were lowered in that layout. With tp
    alone on the sequence the MLP runs as `blocks.mlp_ring` (two chips,
    and four: every chunk of the ring in its place); with sp beside it,
    as the compiler lays it out."""
    from ray_tpu.models import llama

    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(), dtype=jnp.float32, use_ring_attention=ring,
        # the parameters' heads dim is placed over tp: 2 kv heads cannot be
        n_kv_heads=max(2, mesh_cfg.tp))
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                              cfg.vocab_size)
    before = _seq_sharded_boundaries()
    _assert_matches_single_device(cfg, toks, mesh_cfg)
    assert _seq_sharded_boundaries() > before


@pytest.mark.parametrize("seq", [33, 1])
def test_llama_residual_layout_adapts_to_the_sequence(seq):
    """tp 2 divides neither S = 33 nor S = 1 (a decode step): the mesh
    axis is dropped from that dim, the step lowers in the whole-sequence
    layout and matches the single-device program."""
    from ray_tpu.models import llama

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, seq + 1), 0,
                              cfg.vocab_size)
    before = _seq_sharded_boundaries()
    _assert_matches_single_device(cfg, toks, MeshConfig(dp=1, fsdp=2, tp=2))
    assert _seq_sharded_boundaries() == before


def test_seq_sharded_boundaries_counts_lowered_boundaries():
    """`tp.seq_sharded_boundaries`: one a sublayer boundary LOWERED with
    the sequence over tp (the embedding's output and the scanned layer
    body's two: the body lowers once for all layers), none without a tp
    mesh."""
    from ray_tpu.models import llama

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                              cfg.vocab_size)
    before = _seq_sharded_boundaries()
    for mesh_cfg in (None, MeshConfig(dp=4)):
        fn, params, batch = _llama_loss_and_grads(cfg, toks, mesh_cfg)
        fn.lower(params, batch)
    assert _seq_sharded_boundaries() == before
    fn, params, batch = _llama_loss_and_grads(
        cfg, toks, MeshConfig(dp=1, fsdp=2, tp=2))
    fn.lower(params, batch)
    assert _seq_sharded_boundaries() - before >= 3


def test_collective_report_seq_sharded_residual():
    """On a tp 2 mesh the tp boundary is a reduce-scatter and an
    all-gather, against the whole-sequence layout (the same rules with
    "res_seq" over sp alone, which is what the residual had before): the
    activations now reach attention's column-parallel matmuls through
    all-gathers (the MLP's go round `blocks.mlp_ring` as
    collective-permutes), and where the backend forms reduce-scatters the
    all-reduced bytes fall. (XLA's CPU pipeline keeps each as an all-reduce
    whose result it slices; the v5e's compiler fuses them,
    tests/test_tpu_aot_compile.py.)"""
    from ray_tpu.models import llama
    from ray_tpu.parallel.hlo_report import collective_report

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0,
                              cfg.vocab_size)
    mesh_cfg = MeshConfig(dp=1, fsdp=2, tp=2)
    whole = collective_report(*_llama_loss_and_grads(
        cfg, toks, mesh_cfg, LogicalAxisRules().replace(res_seq="sp")))
    sharded = collective_report(*_llama_loss_and_grads(cfg, toks, mesh_cfg))
    # one chip's block of the residual stream, whole sequence
    activation = (8 // 2) * 32 * cfg.d_model * 4
    scattered = sharded["reduce-scatter"]["bytes"]
    assert (sharded["all-gather"]["bytes"] - whole["all-gather"]["bytes"]
            >= 4 * activation)
    assert sharded["collective-permute"]["bytes"] >= 4 * activation // 2
    assert (sharded["all-reduce"]["bytes"] + scattered
            <= whole["all-reduce"]["bytes"] + activation)
    if scattered:
        assert sharded["all-reduce"]["bytes"] < whole["all-reduce"]["bytes"]


def test_llama_ring_attention_mesh():
    import optax
    from ray_tpu.models import llama
    from ray_tpu.train.step import init_train_state, make_train_step

    mesh = build_mesh(MeshConfig(dp=2, sp=2, tp=2))
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), use_ring_attention=True)
    rules = LogicalAxisRules()
    opt = optax.adamw(1e-3)
    state, shardings = init_train_state(
        partial(llama.init, cfg), opt, llama.param_logical_axes(cfg),
        mesh, jax.random.PRNGKey(0), rules,
    )
    bs = logical_sharding(mesh, ("batch", "seq"), rules)
    step = make_train_step(
        partial(llama.loss_fn, config=cfg, mesh=mesh, rules=rules),
        opt, shardings, batch_sharding={"inputs": bs, "targets": bs},
    )
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0, cfg.vocab_size)
    batch = {
        "inputs": jax.device_put(toks[:, :-1], bs),
        "targets": jax.device_put(toks[:, 1:], bs),
    }
    losses = []
    for _ in range(3):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_multislice_mesh_llama_step():
    """Multi-slice story (SURVEY §7): a leading dcn axis spans slices,
    batch shards over (dcn, dp, fsdp), model axes stay intra-slice. On 8
    fake CPU devices: 2 "slices" x (fsdp=2, tp=2)."""
    import dataclasses as _dc
    from functools import partial

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshConfig, build_multislice_mesh
    from ray_tpu.parallel.sharding import LogicalAxisRules, logical_sharding
    from ray_tpu.train.step import init_train_state, make_train_step

    mesh = build_multislice_mesh(
        MeshConfig(dp=1, fsdp=2, tp=2), num_slices=2,
        devices=jax.devices()[:8])
    assert mesh.shape["dcn"] == 2

    rules = LogicalAxisRules()
    bs = logical_sharding(mesh, ("batch", "seq"), rules)
    # the batch axis must span the dcn (inter-slice) axis
    assert "dcn" in (bs.spec[0] if isinstance(bs.spec[0], tuple)
                     else (bs.spec[0],))

    cfg = _dc.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    opt = optax.adamw(1e-3)
    state, shardings = init_train_state(
        partial(llama.init, cfg), opt, llama.param_logical_axes(cfg),
        mesh, jax.random.PRNGKey(0), rules)
    step = make_train_step(
        partial(llama.loss_fn, config=cfg, mesh=mesh, rules=rules),
        opt, shardings, batch_sharding={"inputs": bs, "targets": bs})
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 65), 0,
                              cfg.vocab_size)
    batch = {"inputs": jax.device_put(toks[:, :-1], bs),
             "targets": jax.device_put(toks[:, 1:], bs)}
    state, metrics = step(state, batch)
    loss = float(metrics["loss"])
    assert loss > 0 and loss == loss


def test_multislice_single_slice_falls_back():
    import jax

    from ray_tpu.parallel.mesh import MeshConfig, build_multislice_mesh

    mesh = build_multislice_mesh(MeshConfig(dp=-1), num_slices=1,
                                 devices=jax.devices()[:4])
    assert "dcn" not in mesh.shape and mesh.shape["dp"] == 4
