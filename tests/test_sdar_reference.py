"""Block-diffusion training of the routed-experts decoder (`models/sdar.py`
over `models/mixtral.py`, `parallel/moe.py` and `ops/flash_attention.py`)
against the plain reference `benchmarks/reference_sdar.py`, at tiny sizes on
the CPU, seeded weights. The program runs in float32 here, so that routing
cannot flip between the two: every difference is then summation order.
"""

import dataclasses
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_sdar as ref
from ray_tpu._private import device_profiler
from ray_tpu.models import blocks, llama, mixtral, sdar
from ray_tpu.ops.flash_attention import (
    BlockDiffusion, block_schedule, flash_attention)
from ray_tpu.parallel import moe

# float32 against float32-"highest": ~1e2 additions per output of O(1)
# terms, each rounded to 6e-8. 2e-5 is ~5x what is measured below; a
# bfloat16 matmul anywhere (4e-3 a product) is 200x over it.
RTOL = ATOL = 2e-5

SHARE = dict(n_experts=16, n_experts_held=2, first_expert=4)  # 4-5 of 16
WHOLE = dict(n_experts=8)                                     # all 8


def _model(over=WHOLE, seed=0, **kw):
    cfg = sdar.SdarConfig.tiny(
        vocab_size=256, dtype=jnp.float32, remat=False, loss_chunk_size=8,
        experts_per_token=4, **{**over, **kw})
    params = sdar.init(cfg, jax.random.PRNGKey(seed))
    # norm scales that are not 1, so that a scale applied in the wrong
    # place (or over the wrong channels) shows
    key = jax.random.PRNGKey(seed + 100)

    def rescale(path, w):
        if not path[-1].key.endswith("norm"):
            return w
        # crc32, not hash(): the same weights in every process
        sub = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        return (1.0 + 0.3 * jax.random.normal(sub, w.shape)).astype(w.dtype)

    params = jax.tree_util.tree_map_with_path(rescale, params)
    return cfg, params, dataclasses.asdict(cfg)


def _tokens(seed, rows=2, seq=24):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0, 256)


@pytest.mark.parametrize("over", [SHARE, WHOLE], ids=["share", "whole"])
def test_loss_and_gradients_match_reference(over):
    """The whole model and one chip's share: the loss and every leaf of its
    gradient, the program's kernels' oracle path against the reference's
    dense [2L, 2L] mask and every-expert-on-every-token experts."""
    cfg, params, model = _model(over)
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    toks = _tokens(1)
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(
            lambda p: sdar.loss_fn(p, {"tokens": toks}, cfg))(params)
    want, g_want = jax.value_and_grad(
        lambda p: ref.loss_value(p, toks[:, :-1], model))(params)
    np.testing.assert_allclose(got, want, rtol=RTOL)
    flat_got = jax.tree_util.tree_leaves_with_path(g_got)
    for (path, a), b in zip(flat_got, jax.tree.leaves(g_want)):
        scale = float(jnp.abs(b).max()) + 1e-30
        np.testing.assert_allclose(
            a / scale, b / scale, atol=ATOL,
            err_msg=jax.tree_util.keystr(path))
    # a share's combine weights are constants, but its router still learns
    # from the load-balancing loss
    assert np.any(g_got["layers"]["moe_gate"])
    assert g_got["layers"]["q_norm"].shape == (cfg.n_layers, cfg.d_head)
    assert np.any(g_got["layers"]["q_norm"])


def test_loss_is_masked_weighted_and_over_the_data_tokens():
    """`batch["mask"]` selects the data tokens that count (CE only: the
    router's loss sees every row); with rows masked out the program's loss
    is the reference's over the rows left, given the whole batch's aux."""
    cfg, params, model = _model(SHARE)
    toks = _tokens(2, rows=3)
    x_0 = toks[:, :-1]
    mask = (jnp.arange(3) < 2)[:, None] * jnp.ones((1, x_0.shape[1]))
    with jax.default_matmul_precision("highest"):
        got = sdar.loss_fn(
            params, {"inputs": x_0, "targets": toks[:, 1:], "mask": mask}, cfg)
    ce2, _ = ref.loss_terms(params, x_0[:2], model)
    _, lb3 = ref.loss_terms(params, x_0, model)
    np.testing.assert_allclose(got, ce2 + cfg.aux_loss_coef * lb3, rtol=RTOL)


def test_the_noise_is_a_function_of_the_row_and_the_seed():
    """The program's draw and the reference's own lines agree bit for bit;
    a repeated row repeats its mask; another `noise_seed` or another token
    draws another; p = (1 - eps) t + eps stays in [eps, 1); the share of
    masked tokens follows p."""
    cfg, _, model = _model()
    x_0 = jax.random.randint(jax.random.PRNGKey(3), (64, 512), 0, 256)
    noised, p = sdar.noise(x_0, cfg)
    for r in (0, 17, 63):
        want, want_p = ref.noise(np.asarray(x_0[r]), model)
        np.testing.assert_array_equal(noised[r], want)
        assert float(p[r]) == float(want_p)
    again, _ = sdar.noise(jnp.concatenate([x_0[5:6], x_0[5:6]]), cfg)
    np.testing.assert_array_equal(again[0], noised[5])
    np.testing.assert_array_equal(again[1], noised[5])
    other, other_p = sdar.noise(x_0, dataclasses.replace(cfg, noise_seed=1))
    assert not np.array_equal(other, noised) and not np.array_equal(other_p, p)
    moved, _ = sdar.noise(x_0.at[0, 0].add(1), cfg)
    assert not np.array_equal(moved[0], noised[0])
    np.testing.assert_array_equal(moved[1:], noised[1:])
    assert cfg.noise_eps <= float(p.min()) and float(p.max()) < 1.0
    assert 0.4 < float(p.mean()) < 0.6
    np.testing.assert_allclose(noised.mean(axis=1), p, atol=0.08)
    # a data pipeline's own draw is used when the batch carries one
    cfg2, params, _ = _model()
    toks = _tokens(4)
    mine = {"inputs": toks[:, :-1], "noise_mask": jnp.ones_like(toks[:, :-1]),
            "noise_p": jnp.ones((2,))}
    assert not np.isclose(sdar.loss_fn(params, mine, cfg2),
                          sdar.loss_fn(params, {"tokens": toks}, cfg2))


def test_eight_shares_make_the_whole_layer():
    """The guide's share test: the routed parts that all 8 shares give add
    up to the uncut layer's (no part is computed by every chip alike here:
    no shared expert)."""
    cfg, params, model = _model(dict(n_experts=16))
    p = jax.tree.map(lambda a: a[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(3), (64, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want, aux = moe.moe_layer(h, p["moe_gate"], p["experts"],
                                  cfg.experts_per_token, cfg.norm_topk_prob)
        chosen = np.asarray(aux.experts)
        n_shares, per = 8, cfg.n_experts // 8
        total, live = jnp.zeros_like(h), 0
        for i in range(n_shares):
            held = jax.tree.map(lambda a: a[i * per:(i + 1) * per],
                                p["experts"])
            y, aux_i = moe.moe_layer(
                h, p["moe_gate"], held, cfg.experts_per_token,
                cfg.norm_topk_prob, held=(i * per, per))
            np.testing.assert_array_equal(aux_i.experts, chosen)
            total = total + y
            live += int(np.sum((chosen >= i * per) & (chosen < (i + 1) * per)))
    assert live == chosen.size          # every pair is some share's
    np.testing.assert_allclose(total, want, rtol=RTOL, atol=ATOL)
    # and the uncut layer is the reference's: one layer, x in, x out
    x = jax.random.normal(jax.random.PRNGKey(4), (16, cfg.d_model))
    mask = jnp.asarray(ref.visible(8, cfg.block))
    pos = jnp.tile(jnp.arange(8), 2)
    with jax.default_matmul_precision("highest"):
        want_x, _, _ = ref._layer(x, p, pos, mask, model)
        got = blocks.attn_sublayer(x[None], p, pos[None], cfg,
                                   mask=BlockDiffusion(8, cfg.block))
        routed, _ = mixtral.moe_block(
            blocks.rms_norm(got, p["mlp_norm"], cfg.norm_eps), p, cfg, None)
    np.testing.assert_allclose((got + routed)[0], want_x, rtol=RTOL, atol=ATOL)


def test_the_clean_half_does_not_depend_on_the_noised_half():
    """x_0 rows never see x_t columns: another x_t leaves the x_0 half's
    hidden states as they are, bit for bit; the x_t half moves."""
    cfg, params, _ = _model(SHARE)
    x_0 = _tokens(5)[:, :-1]
    _, _, x_t = sdar.noised_batch({}, x_0, cfg)
    other = jnp.where(x_t == cfg.mask_id, x_0, cfg.mask_id)
    length = x_0.shape[1]
    a, _ = sdar.hidden_states(params, x_t, x_0, cfg)
    b, _ = sdar.hidden_states(params, other, x_0, cfg)
    np.testing.assert_array_equal(a[:, length:], b[:, length:])
    assert not np.allclose(a[:, :length], b[:, :length])
    # and the program's hidden states are the reference's, both halves
    with jax.default_matmul_precision("highest"):
        got, _ = sdar.hidden_states(params, x_t, x_0, cfg)
    np.testing.assert_allclose(
        got, ref.hidden_states(params, x_0, dataclasses.asdict(cfg)),
        rtol=1e-4, atol=1e-4)


def test_at_block_one_the_clean_half_is_the_causal_model():
    """With blocks of one token the x_0 half's mask is the causal one, and
    its positions are 0..L-1: its hidden states equal
    `mixtral.forward_hidden` on x_0 alone, the path every other cell runs."""
    cfg, params, _ = _model(WHOLE, block=1)
    x_0 = _tokens(6)[:, :-1]
    _, _, x_t = sdar.noised_batch({}, x_0, cfg)
    length = x_0.shape[1]
    with jax.default_matmul_precision("highest"):
        x, _ = sdar.hidden_states(params, x_t, x_0, cfg)
        want, _ = mixtral.forward_hidden(params, x_0, cfg)
    got = blocks.rms_norm(x[:, length:], params["final_norm"], cfg.norm_eps)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("form", ["per_head", "all_channels"])
def test_qk_norm_by_the_shape_of_its_scale(form):
    """`blocks.qk_norm` reads the form off the scale: [D] is Qwen3's
    RMSNorm of every head over its own D channels (one scale for all
    heads), [H, D] OLMoE's one RMSNorm over all H x D channels, unchanged."""
    cfg = llama.LlamaConfig.tiny()
    cfg = dataclasses.replace(cfg, qk_norm=True, dtype=jnp.float32)
    b, s, h, kv, d = 2, 5, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, h, d)) * 3.0
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, kv, d)) * 0.2
    shape = (lambda n: (d,)) if form == "per_head" else (lambda n: (n, d))
    g_q = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), shape(h))
    g_k = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(3), shape(kv))
    got_q, got_k = blocks.qk_norm(q, k, {"q_norm": g_q, "k_norm": g_k}, cfg)

    def want(x, g):
        x = np.asarray(x, np.float64)
        over = (-1,) if form == "per_head" else (-2, -1)
        rms = np.sqrt(np.mean(x * x, axis=over, keepdims=True) + cfg.norm_eps)
        return x / rms * np.asarray(g)

    np.testing.assert_allclose(got_q, want(q, g_q), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_k, want(k, g_k), rtol=1e-5, atol=1e-6)
    if form == "per_head":
        # every head of unit RMS before the scale: a head 15x larger than
        # its neighbour comes out the same size
        np.testing.assert_allclose(
            np.sqrt(np.mean(np.square(got_q / g_q), -1)), 1.0, rtol=1e-4)


def test_routing_stats_counts_the_held_pairs_of_every_layer():
    cfg, params, model = _model(SHARE)
    toks = _tokens(7, rows=3)
    live = sdar.routing_stats(params, toks, cfg)
    chosen = np.asarray(ref.routing(params, toks[:, :-1], model))
    first, n = cfg.held
    want = ((chosen >= first) & (chosen < first + n)).sum(axis=(1, 2))
    np.testing.assert_array_equal(live, want)
    assert chosen.shape == (cfg.n_layers, 3 * 2 * 24, cfg.experts_per_token)
    # and over the rows of the capacity each layer runs at
    caps = np.asarray(moe.share_capacities(
        3 * 2 * 24, cfg.experts_per_token, n, cfg.n_experts))
    np.testing.assert_allclose(
        sdar.routing_loads(params, toks, cfg),
        [rows / caps[np.sum(rows >= caps[:-1])] for rows in want], rtol=1e-6)


def test_seeded_weights_have_the_scales_the_cell_counts_on():
    """A data token's embedding row has unit RMS, the MASK token's
    d_model ** -0.5 (`sdar.init` says why), QK-norm scales are per head."""
    cfg = sdar.SdarConfig.tiny(vocab_size=300, d_model=256)
    params = sdar.init(cfg, jax.random.PRNGKey(0))
    rms = np.sqrt(np.mean(np.square(
        np.asarray(params["embed"], np.float32)), axis=1))
    assert cfg.mask_id == 299
    np.testing.assert_allclose(rms[:-1], 1.0, atol=0.2)
    np.testing.assert_allclose(rms[-1], 256 ** -0.5, rtol=0.2)
    assert params["layers"]["q_norm"].shape == (cfg.n_layers, cfg.d_head)
    assert params["layers"]["k_norm"].shape == (cfg.n_layers, cfg.d_head)
    axes = sdar.param_logical_axes(cfg)
    assert axes["layers"]["q_norm"] == ("layers", "kv")
    jax.tree.map(lambda a, ax: len(ax) == a.ndim or pytest.fail(str(ax)),
                 params, axes, is_leaf=lambda x: isinstance(x, tuple))


def test_counters_and_scopes_of_a_lowering():
    cfg, params, _ = _model(SHARE)
    toks = _tokens(11)
    length = toks.shape[1] - 1
    before = dict(device_profiler.snapshot()["counters"])
    lowered = jax.jit(jax.grad(
        lambda p: sdar.loss_fn(p, {"tokens": toks}, cfg))).lower(params)
    after = device_profiler.snapshot()["counters"]
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    assert delta["bd.block"] == cfg.block
    assert delta["bd.rows_noised"] == delta["bd.rows_clean"] == 2 * length
    # the scanned layers lower once
    assert delta["moe.experts_held"] == 2
    assert delta["moe.rows_capacity"] == 2 * 2 * length * cfg.experts_per_token
    assert delta["moe.experts"] == cfg.n_experts
    # a share counts by comparison at ONE site, the load-balance term's
    # shares (`sort_held` has its groups from the sorted keys)
    assert delta["moe.counts_by_comparison"] == 1
    text = lowered.as_text(debug_info=True)
    for scope in ("bd.noise", "bd.attend", "bd.loss"):
        assert scope in text, scope
    # the kernels' path counts the tiles the rule lets it skip
    before = dict(device_profiler.snapshot()["counters"])
    plans = block_schedule(256, 256, 128, 128, BlockDiffusion(128, 4))
    q = jnp.zeros((1, 256, 2, 16))
    jax.grad(lambda q: flash_attention(
        q, q, q, mask=BlockDiffusion(128, 4), interpret=True, block_q=128,
        block_k=128).sum())(q)
    after = device_profiler.snapshot()["counters"]
    assert after["flash.tiles_skipped"] - before.get(
        "flash.tiles_skipped", 0) == sum(
            plans[k].steps_skipped for k in ("fwd", "dq", "dkv")) == 3


def test_trainer_step_runs_the_objective_on_a_mesh():
    """`make_train_step` with the module's `loss_fn` on the CPU test mesh
    (fsdp 2 x tp 2): the loss is the one-device loss and falls on a
    repeated batch, whose mask repeats."""
    import optax

    from ray_tpu import train
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import LogicalAxisRules

    cfg, params, _ = _model(SHARE)
    mesh = build_mesh(MeshConfig(dp=1, fsdp=2, tp=2),
                      devices=jax.devices()[:4])
    rules = LogicalAxisRules()
    toks = _tokens(12, rows=4, seq=32)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    np.testing.assert_allclose(
        jax.jit(lambda p: sdar.loss_fn(p, batch, cfg, mesh, rules))(params),
        sdar.loss_fn(params, batch, cfg), rtol=1e-4)
    opt = optax.adamw(3e-3)
    state, shardings = train.init_train_state(
        partial(sdar.init, cfg), opt, sdar.param_logical_axes(cfg), mesh,
        jax.random.PRNGKey(0), rules)
    bs = train.batch_sharding(mesh, rules)
    step = train.make_train_step(
        partial(sdar.loss_fn, config=cfg, mesh=mesh, rules=rules), opt,
        shardings, batch_sharding={"inputs": bs, "targets": bs})
    batch = jax.device_put(batch, bs)
    losses = []
    for _ in range(4):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
