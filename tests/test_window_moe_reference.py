"""The model of window and full attention layers in a pattern
(`models/window_moe.py` over `models/llama.py`'s attention sublayer,
`models/mla_moe.py`'s expert sublayer and `ops/flash_attention.py`'s window
rule) against the plain reference `benchmarks/reference_laguna.py`, at tiny
sizes on the CPU, seeded weights. The program runs in float32 here, so that
routing cannot flip between the two: every difference is then summation
order. The same module run as SmallThinker-21BA3B (no dense layer, a full
layer first, no rotary embedding in the full layers, the router ahead of the
attention, ReGLU experts and no shared one) against
`benchmarks/reference_smallthinker.py`, at the end.
"""

import dataclasses
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_laguna as ref
from benchmarks import reference_smallthinker as ref_st
from ray_tpu._private import device_profiler
from ray_tpu.models import blocks, experts, window_moe
from ray_tpu.models.window_moe import FULL, SLIDING

# float32 against float32-"highest": ~1e2 additions per output of O(1)
# terms, each rounded to 6e-8 (see tests/test_mla_moe_reference.py). 2e-5 is
# 5x what is measured; a bfloat16 matmul anywhere (4e-3 a product) is 200x
# over it, which `test_bfloat16_where_float32_is_stated_fails` shows.
RTOL = ATOL = 2e-5
# a gradient leaf, over its largest entry: sums over tokens of both signs
GRAD_ATOL = 4e-5

CUT = dict(layers=(0, 1, 2, 3, 4))    # the dense layer + one period
SHARE = dict(n_experts_held=4, first_expert=4)


def _model(seed=0, preset=window_moe.WindowMoeConfig.tiny, **over):
    cfg = preset(
        vocab_size=256, dtype=jnp.float32, remat=False, loss_chunk_size=16,
        **over)
    params = window_moe.init(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 100)

    def rescale(path, w):
        # norm scales that are not 1, so that a scale applied in the wrong
        # place shows; crc32, not hash(): the same weights in every process
        sub = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        if path[-1].key.endswith("norm"):
            return (1.0 + 0.3 * jax.random.normal(sub, w.shape)).astype(w.dtype)
        return w

    params = jax.tree_util.tree_map_with_path(rescale, params)
    return cfg, params, dataclasses.asdict(cfg)


def _tokens(seed, rows=2, seq=24):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0, 256)


def _loss_and_gradients(cfg, params, toks):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: window_moe.loss_fn(p, {"tokens": toks}, cfg)))(params)


def _assert_loss_and_gradients(cfg, params, model, toks, atol=GRAD_ATOL,
                               reference=ref):
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    got, g_got = _loss_and_gradients(cfg, params, toks)
    want, g_want = jax.value_and_grad(lambda p: reference.loss_value(
        p, toks[:, :-1], toks[:, 1:], model))(params)
    np.testing.assert_allclose(got, want, rtol=RTOL)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree.leaves(g_want)):
        scale = float(jnp.abs(b).max()) + 1e-30
        np.testing.assert_allclose(a / scale, b / scale, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("over", [
    {**CUT, **SHARE}, CUT, dict(CUT, score="softmax", norm_topk_prob=False),
    dict(CUT, attn_gate=False, qk_norm=True)],
    ids=["share", "whole", "softmax_scores", "qk_norm_no_gate"])
def test_a_dense_layer_and_a_period_match_the_reference(over):
    """Loss and every gradient leaf; the sequence (24) is three windows (8)
    long, so the window cuts most rows. The last two cases are the
    configuration's `assumed` readings switched to their alternatives."""
    cfg, params, model = _model(**over)
    assert cfg.plan()[3] == [("dense", 1), ("periods", 1)]
    _assert_loss_and_gradients(cfg, params, model, _tokens(1))


def test_the_whole_published_pattern_matches_the_reference():
    """40 layers as published: layer 0 dense and full, nine scanned periods
    (sliding x 3, full) from layer 1 on, layers 37-39 unrolled; every kind
    of layer at its published index, at its own number of heads."""
    published = window_moe.WindowMoeConfig()
    cfg, params, model = _model(
        layer_types=published.layer_types,
        heads_per_layer=tuple(4 if t == FULL else 6
                              for t in published.layer_types),
        mlp_layer_types=published.mlp_layer_types)
    assert cfg.plan() == ([0], [37, 38, 39], list(range(1, 37, 4)),
                          [("dense", 1), ("periods", 9), ("loose", 3)])
    assert [i for i, t in enumerate(cfg.layer_types) if t == FULL] \
        == list(range(0, 40, 4))
    assert params["periods"]["sliding"]["wq"].shape == (9, 3, 64, 6, 16)
    assert params["periods"]["full"]["wq"].shape == (9, 64, 4, 16)
    assert params["loose"]["sliding_sparse"]["wq"].shape[0] == 3
    assert params["loose"]["full_dense"]["w_gate"].shape == (1, 64, 128)
    # every sublayer's output projection at 1 / sqrt(2 x 40) of its draw,
    # as a model this deep is initialised (GPT-2's rule): at the plain draw
    # 40 branches as large as the stream amplify a float32 rounding ~1e4-fold
    # (tests/test_hybrid_moe_reference.py has the readings). A layer at the
    # wrong index or of the wrong kind still moves whole leaves by O(1)
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w * (2 * 40) ** -0.5
        if path[-1].key in ("wo", "w_down") else w, params)
    _assert_loss_and_gradients(cfg, params, model, _tokens(2, rows=1, seq=20),
                               atol=2e-4)


def test_scanned_periods_equal_the_same_layers_unrolled():
    """Layer by layer: the reference's own reading of the stacks gives each
    published layer its parameters, and `_layer` of that layer's kind run on
    them in order is what the scans computed, choices and all."""
    cfg, params, model = _model(layers=tuple(range(0, 12)))
    assert cfg.plan()[3] == [("dense", 1), ("periods", 2), ("loose", 3)]
    toks = _tokens(3)[:, :-1]
    with jax.default_matmul_precision("highest"):
        got, chosen = window_moe.forward_hidden(params, toks, cfg)
        x = params["embed"][toks]
        positions = jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)
        n = 0
        for i, p in ref.layer_params(params, model):
            x, e = window_moe.layer(x, p, positions, cfg, None, None,
                                     *cfg.kind(i))
            if e is not None:
                np.testing.assert_array_equal(e, chosen[n])
                n += 1
        x = blocks.rms_norm(x, params["final_norm"], cfg.norm_eps)
    assert n == 11
    np.testing.assert_allclose(got, x, rtol=RTOL, atol=ATOL)


def test_the_window_matters():
    """The same weights under a reference whose sliding layers see every
    earlier key: far outside the tolerance, so a program that ran its
    sliding layers causal would not pass."""
    cfg, params, model = _model(**CUT)
    toks = _tokens(4)
    want = ref.loss_value(params, toks[:, :-1], toks[:, 1:], model)
    full = ref.loss_value(params, toks[:, :-1], toks[:, 1:],
                          dict(model, window=10**9))
    assert abs(float(full) - float(want)) > 100 * RTOL * float(want)
    got, _ = _loss_and_gradients(cfg, params, toks)
    np.testing.assert_allclose(got, want, rtol=RTOL)


def test_bfloat16_where_float32_is_stated_fails():
    """The tolerances are tight enough: ONE projection of one layer rounded
    to bfloat16 (its weights, not even its products) breaks the loss's."""
    cfg, params, model = _model(**CUT)
    toks = _tokens(5)
    want = ref.loss_value(params, toks[:, :-1], toks[:, 1:], model)
    rounded = jax.tree_util.tree_map_with_path(
        lambda path, w: w.astype(jnp.bfloat16).astype(w.dtype)
        if path[-1].key == "wq" else w, params)
    got, _ = _loss_and_gradients(cfg, rounded, toks)
    assert abs(float(got) - float(want)) > 3 * RTOL * float(want)


def test_remat_changes_nothing():
    cfg, params, _ = _model(**CUT, **SHARE)
    toks = _tokens(6)
    plain = _loss_and_gradients(cfg, params, toks)
    remat = _loss_and_gradients(dataclasses.replace(cfg, remat=True), params,
                                toks)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(remat)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# --------------------------------------------------------------------------
# the rotary forms
# --------------------------------------------------------------------------

def test_yarn_table_at_the_published_numbers():
    """low 5, high 16 and the 32 frequencies of the full layers' 64 rotary
    channels: the program's table against the reference's own lines, and
    both against numbers worked by hand."""
    cfg = window_moe.WindowMoeConfig()
    rotary = cfg.rotary("full")
    assert rotary == blocks.Rotary(500000.0, 64, (64, 4096, 64, 1),
                                  1.4158883083359672)
    assert rotary.attention_factor == pytest.approx(0.1 * np.log(64) + 1)
    assert ref.yarn_bounds(64, 500000, 4096, 64, 1) == (5, 16)
    want = ref.yarn_inv_freq(64, 500000, 64, 4096, 64, 1)
    plain = 500000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(want[:6], plain[:6], rtol=1e-12)
    np.testing.assert_allclose(want[16:], plain[16:] / 64, rtol=1e-12)
    # pair 10, 5 / 11 up the ramp
    assert want[10] == pytest.approx(
        plain[10] * (6 / 11) + plain[10] / 64 * (5 / 11), rel=1e-12)
    got = rotary.inv_freq(128)
    assert got.shape == (32,) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-7)
    table, factor = ref.rope_table(dataclasses.asdict(cfg), FULL)
    np.testing.assert_array_equal(got, table)
    assert factor == rotary.attention_factor
    # the sliding layers: the whole head at theta 10,000, no factor
    assert cfg.rotary("sliding") == blocks.Rotary(10000.0, 128, None, 1.0)
    np.testing.assert_allclose(
        cfg.rotary("sliding").inv_freq(128),
        10000.0 ** (-np.arange(64) / 64), rtol=1e-6)


def test_partial_rotary_leaves_the_other_channels_untouched():
    """Full layers: channels 0-63 of a head turn (d with d + 32) and carry
    `attention_factor`; channels 64-127 pass through bit for bit. With the
    whole head and no factor `Rotary` is the plain `_rope`."""
    cfg = window_moe.WindowMoeConfig()
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 3, 128))
    positions = jnp.broadcast_to(jnp.arange(40), (2, 40))
    got = blocks.rope(x, positions, 500000.0, cfg.rotary("full"))
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    table, factor = ref.rope_table(dataclasses.asdict(cfg), FULL)
    want = jax.vmap(lambda row: ref._rope(row, table, factor))(x)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # position 0 turns nothing: the rotated channels are x times the factor
    np.testing.assert_allclose(got[:, 0, :, :64], factor * x[:, 0, :, :64],
                               rtol=1e-6)
    # (the table made in float64 and rounded once, `_rope`'s in float32)
    np.testing.assert_allclose(
        blocks.rope(x, positions, 10000.0, blocks.Rotary(10000.0, 128)),
        blocks.rope(x, positions, 10000.0), atol=1e-5)


# --------------------------------------------------------------------------
# the share, the counters, the contract's other parts
# --------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of the 4 shares (4 experts each of 16) plus the
    shared expert ONCE are the uncut reference's whole layer."""
    cfg, params, model = _model(**CUT)
    p = jax.tree.map(lambda a: a[0, 0], params["periods"]["sliding"])
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        h = ref._rms(x[0], p["mlp_norm"], cfg.norm_eps)
        routed, shared, chosen = ref.experts(h, p, model)
        whole = x[0] + routed + shared
        total = x[0] + shared
        for first in range(0, 16, 4):
            share = dataclasses.replace(cfg, n_experts_held=4,
                                        first_expert=first)
            part = dict(p, experts=jax.tree.map(
                lambda a: a[first:first + 4], p["experts"]))
            y, e = experts.expert_sublayer(x, part, share)
            np.testing.assert_array_equal(e, chosen)
            total = total + (y[0] - x[0] - shared)
    np.testing.assert_allclose(total, whole, rtol=RTOL, atol=ATOL)


def test_lowering_counters():
    """Per lowering: one scan over the cut's period; the window call once,
    in the scanned sliding body (traced once for its three layers), and
    none for the two full layers; a routed block counts its pairs by
    comparison (one call a body: the sliding body's and the full layer's)."""
    cfg, params, _ = _model(**CUT, **SHARE)
    before = device_profiler.snapshot()["counters"]
    jax.jit(lambda p, t: window_moe.forward_hidden(p, t, cfg)[0]).lower(
        params, _tokens(0)[:, :-1])
    after = device_profiler.snapshot()["counters"]
    grew = lambda k: after.get(k, 0) - before.get(k, 0)  # noqa: E731
    assert grew("pattern.periods") == 1
    assert grew("pattern.layers_unrolled") == 1
    assert grew("flash.window_calls") == 1
    assert grew("moe.counts_by_comparison") == 2
    assert grew("moe.experts_held") == 2 * 4


def test_param_axes_match_the_parameters():
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    for over in (CUT, dict(), dict(qk_norm=True, attn_gate=False)):
        cfg, params, _ = _model(**over)
        axes = window_moe.param_logical_axes(cfg)
        assert jax.tree.structure(params) == jax.tree.structure(
            axes, is_leaf=is_axes)
        for a, ax in zip(jax.tree.leaves(params),
                         jax.tree.leaves(axes, is_leaf=is_axes)):
            assert a.ndim == len(ax)
        assert sum(a.size for a in jax.tree.leaves(params)) \
            == cfg.num_params()


def test_the_published_count_of_parameters():
    """33,442,596,864 whole (the published 33.4B), 1,252,071,424 as the
    cell holds it; an element-wise output gate would make it 34.1B."""
    whole = window_moe.WindowMoeConfig()
    assert whole.num_params() == 33_442_596_864
    assert window_moe.attn_num_params(whole, 64) == 37_879_808
    assert window_moe.attn_num_params(whole, 48) == 29_458_432
    cell = dataclasses.replace(whole, layers=tuple(range(9)),
                               n_experts_held=32, vocab_size=12_544)
    assert cell.num_params() == 1_252_071_424
    assert cell.plan()[3] == [("dense", 1), ("periods", 2)]
    shapes = jax.eval_shape(lambda: window_moe.init(cell, jax.random.PRNGKey(0)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 1_252_071_424
    assert shapes["periods"]["sliding"]["wq"].shape == (2, 3, 2048, 64, 128)
    assert shapes["periods"]["full"]["wq"].shape == (2, 2048, 48, 128)
    elementwise = sum(2048 * h * 128 - 2048 * h for h in whole.heads_per_layer)
    assert round((33_442_596_864 + elementwise) / 1e9, 1) == 34.1


def test_patterns_the_program_does_not_run_are_refused():
    tiny = window_moe.WindowMoeConfig.tiny
    uneven = (FULL,) + (SLIDING,) * 4 + (FULL,) + (SLIDING,) * 5 + (FULL,)
    with pytest.raises(NotImplementedError, match="period"):
        tiny(layer_types=uneven,
             heads_per_layer=tuple(4 if t == FULL else 6 for t in uneven))
    with pytest.raises(NotImplementedError, match="period"):
        tiny(layer_types=(FULL,) * 12, heads_per_layer=(4,) * 12)
    with pytest.raises(NotImplementedError, match="heads"):
        tiny(heads_per_layer=(4, 6, 6, 8) * 3)
    with pytest.raises(NotImplementedError, match="dense"):
        tiny(mlp_layer_types=("dense", "sparse", "dense") + ("sparse",) * 9
             ).n_dense_layers
    with pytest.raises(ValueError, match="layers"):
        tiny(layers=(3, 2))


def test_an_ep_mesh_axis_is_refused():
    cfg, params, _ = _model(**CUT)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("ep",))
    with pytest.raises(NotImplementedError, match="ep"):
        window_moe.forward_hidden(params, _tokens(0)[:, :-1], cfg, mesh)


# --------------------------------------------------------------------------
# the same module as SmallThinker-21BA3B: the router AHEAD of the attention
# --------------------------------------------------------------------------

def _ahead(seed=0, **over):
    """SmallThinker's pattern at toy widths
    (`WindowMoeConfig.tiny_ahead`)."""
    return _model(seed, window_moe.WindowMoeConfig.tiny_ahead, **over)


@pytest.mark.parametrize("over, plan", [
    (SHARE, [("loose", 4)]),
    (dict(layers=(0, 1, 2, 3, 4)), [("loose", 1), ("periods", 1)]),
    (dict(router_input="residual"), [("loose", 4)]),
    (dict(router_input="ffn_input", expert_form="swiglu"), [("loose", 4)])],
    ids=["share", "whole_and_scanned", "router_reads_the_residual",
         "router_reads_the_ffn_input_swiglu"])
def test_a_router_ahead_of_attention_matches_the_reference(over, plan):
    """Loss and every gradient leaf against `reference_smallthinker`, in the
    three readings of what the router reads (the last with the experts'
    other form): the cell's cut (published layers 0-3, one chip's share:
    the combine weights constants), and every expert held, where the
    router trains and its gradient reaches `attn_norm` and the residual
    through h, layer 0 unrolled before one scanned period."""
    cfg, params, model = _ahead(**over)
    assert cfg.plan()[3] == plan and cfg.n_dense_layers == 0
    assert "shared" not in params["loose"]["full_sparse"]
    assert cfg.rotary("full").theta == 0 \
        and cfg.rotary("sliding") == blocks.Rotary(1_500_000.0, 16, None, 1.0)
    _assert_loss_and_gradients(cfg, params, model, _tokens(7),
                               reference=ref_st)


def test_the_routers_gradient_reaches_the_attentions_norm_through_h():
    """One layer whose V projection is zero: the attention adds nothing,
    so what reaches `attn_norm` comes through the ROUTER alone. Every expert
    held and the router ahead: a gradient, the reference's; the router on
    the feed-forward's input, or a share (constant weights): exactly none.
    And the readings differ: the same weights under the reference's other
    readings are outside the tolerance even here, where the three tensors
    differ by a norm's scales alone (the attention adds nothing)."""
    toks = _tokens(9, rows=1)

    def norm_gradient(**over):
        cfg, params, model = _ahead(layers=(1,), **over)
        params["loose"]["sliding_sparse"]["wv"] *= 0
        grad = lambda f: jax.jit(jax.grad(f))(params)[  # noqa: E731
            "loose"]["sliding_sparse"]["attn_norm"]
        with jax.default_matmul_precision("highest"):
            got = grad(lambda p: window_moe.loss_fn(p, {"tokens": toks}, cfg))
        return got, model, params, grad

    got, model, params, grad = norm_gradient()
    want = grad(lambda p: ref_st.loss_value(p, toks[:, :-1], toks[:, 1:],
                                            model))
    assert float(jnp.abs(want).max()) > 1e-4
    np.testing.assert_allclose(got, want, atol=GRAD_ATOL * float(
        jnp.abs(want).max()))
    assert not np.any(norm_gradient(router_input="ffn_input")[0])
    assert not np.any(norm_gradient(**SHARE)[0])
    losses = [float(ref_st.loss_value(
        params, toks[:, :-1], toks[:, 1:], dict(model, router_input=reads)))
        for reads in ("attention_input", "residual", "ffn_input")]
    assert min(abs(a - b) for a in losses for b in losses if a is not b) \
        > 10 * RTOL * losses[0]


def test_the_four_shares_routed_parts_add_up_without_a_shared_expert():
    """The routed parts of the 4 shares (experts 0-3, 4-7, 8-11, 12-15) of
    a layer routed AHEAD (the choice from one tensor, the rows dispatched
    another) are the uncut reference's whole layer: nothing is counted once
    beside them."""
    cfg, params, model = _ahead()
    p = jax.tree.map(lambda a: a[0], params["loose"]["sliding_sparse"])
    x, read = jax.random.normal(jax.random.PRNGKey(8), (2, 1, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        u = ref._rms(x[0], p["mlp_norm"], cfg.norm_eps)
        dense_w, chosen = ref_st.route(read[0], p, model)
        whole = x[0] + ref_st.experts(u, dense_w, p, model)
        routing = experts.routing(read[0], p, cfg)
        total = x[0]
        for first in range(0, 16, 4):
            share = dataclasses.replace(cfg, n_experts_held=4,
                                        first_expert=first)
            part = dict(p, experts=jax.tree.map(
                lambda a: a[first:first + 4], p["experts"]))
            y, e = experts.expert_sublayer(x, part, share, ahead=routing,
                                            form="reglu")
            np.testing.assert_array_equal(e, chosen)
            total = total + (y[0] - x[0])
    np.testing.assert_allclose(total, whole, rtol=RTOL, atol=ATOL)


def test_the_published_pattern_without_a_dense_layer():
    """`plan()` with no dense layer and a full layer FIRST: layer 0
    unrolled, twelve scanned periods (window x 3, full) from layer 1 on,
    layers 49-51 unrolled; the cell's cut, layers 0-3, four unrolled
    layers. 21,506,562,560 parameters whole (the published 21B),
    656,529,920 as the cell holds it; the counters of one lowering."""
    layout = tuple(int(i % 4 != 0) for i in range(52))
    theta = {"rope_theta": 1_500_000}
    whole = window_moe.WindowMoeConfig(
        vocab_size=151_936, d_model=2560, layer_types=layout,
        rope_layout=layout, heads_per_layer=(28,) * 52,
        mlp_layer_types=("sparse",) * 52, n_kv_heads=4, d_head=128,
        window=4096, rope_parameters={FULL: theta, SLIDING: theta},
        attn_gate=False, d_ff_expert=768, d_ff_shared=0, n_experts=64,
        n_experts_held=64, experts_per_token=6, score="softmax",
        routed_scaling_factor=1.0, router_input="attention_input",
        expert_form="reglu")
    assert whole.layer_types[:5] == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert whole.plan() == ([], [0, 49, 50, 51], list(range(1, 49, 4)),
                            [("loose", 1), ("periods", 12), ("loose", 3)])
    assert whole.num_params() == 52 * 398_627_840 + 777_914_880 \
        == 21_506_562_560
    cell = dataclasses.replace(whole, layers=(0, 1, 2, 3), n_experts_held=16,
                               vocab_size=37_984)
    assert cell.plan() == ([], [0, 1, 2, 3], [], [("loose", 4)])
    assert cell.num_params() == 4 * 115_512_320 + 194_480_640 == 656_529_920
    shapes = jax.eval_shape(lambda: window_moe.init(cell, jax.random.PRNGKey(0)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 656_529_920
    assert shapes["loose"]["sliding_sparse"]["wq"].shape == (3, 2560, 28, 128)
    assert set(shapes["loose"]["full_sparse"]) == {
        "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "router", "experts"}
    axes = window_moe.param_logical_axes(cell)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    mixed = list(layout)
    mixed[5] = 0   # a window layer without RoPE among those with
    with pytest.raises(NotImplementedError, match="rotary"):
        dataclasses.replace(whole, rope_layout=tuple(mixed))
    with pytest.raises(NotImplementedError, match="shared"):
        dataclasses.replace(whole, d_ff_shared=768)
    cfg, params, _ = _ahead(**SHARE)
    before = device_profiler.snapshot()["counters"]
    jax.jit(lambda p, t: window_moe.forward_hidden(p, t, cfg)[0]).lower(
        params, _tokens(0)[:, :-1])
    after = device_profiler.snapshot()["counters"]
    grew = lambda k: after.get(k, 0) - before.get(k, 0)  # noqa: E731
    assert grew("moe.routed_ahead") == 4 == grew("pattern.layers_unrolled")
    assert grew("flash.window_calls") == 3 and grew("pattern.periods") == 0
    assert grew("moe.experts_held") == 4 * 4 and grew("moe.gmm_calls") == 4 * 3
