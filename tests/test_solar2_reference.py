"""The gated-GQA / KDA pattern model (`models/solar_open2.py`: Solar-Open2's
language model by config) over `blocks.attn_sublayer`, `mixers.kda_sublayer`
(softplus decay with no lower bound, low-rank gates, beta in (0, 2)),
`experts.py` and `layer_pattern.py`, against the plain reference
`benchmarks/reference_solar2.py`, at tiny sizes on the CPU, seeded weights.
The program runs in float32 here, so that routing cannot flip between the
two: every difference is summation order.
"""

import dataclasses
import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import opcount_solar2, reference_solar2 as ref
from ray_tpu._private import device_profiler
from ray_tpu.models import blocks, experts, layer_pattern, solar_open2

# float32 against float32-"highest" (tests/test_hybrid_moe_reference.py)
RTOL = ATOL = 2e-5
# a gradient leaf: the chunked delta rule's solve and its level factors
# lose a few bits more than a plain sum (tests/test_hybrid_moe_reference.py)
GRAD_ATOL = 6e-5

PERIOD = dict(layers=(0, 1, 2, 3))    # published layers 0-3: one period
SHARE = dict(n_experts_held=4, first_expert=4)
# every parameter name of the model: a test a name
NAMES = ("embed", "final_norm", "lm_head", "attn_norm", "wq", "wk", "wv",
         "wo", "w_attn_gate", "mlp_norm", "router", "router_bias", "w_gate",
         "w_up", "w_down", "conv_q", "conv_k", "conv_v", "w_f", "w_f_down",
         "dt_bias", "a_log", "w_b", "w_g", "w_g_down", "o_norm")
CASES = {"whole_model": {}, "one_period": PERIOD,
         "share": {**PERIOD, **SHARE}, "starts_at_layer_1":
         dict(layers=(1, 2, 3, 4), **SHARE)}


def _model(seed=0, **over):
    cfg = solar_open2.SolarOpen2Config.tiny(
        vocab_size=256, dtype=jnp.float32, remat=False, loss_chunk_size=16,
        **over)
    params = solar_open2.init(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 100)

    def rescale(path, w):
        name = path[-1].key
        sub = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        if name.endswith("norm"):
            return (1.0 + 0.3 * jax.random.normal(sub, w.shape)).astype(w.dtype)
        if name == "router_bias":
            return 0.2 * jax.random.normal(sub, w.shape)
        return w

    params = jax.tree_util.tree_map_with_path(rescale, params)
    return cfg, params, dataclasses.asdict(cfg)


def _tokens(seed, rows=2, seq=80):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0, 256)


@functools.lru_cache(maxsize=None)
def _loss_and_gradients(case):
    cfg, params, model = _model(**CASES[case])
    toks = _tokens(1)
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(
            lambda p: solar_open2.loss_fn(p, {"tokens": toks}, cfg)))(params)
    want = jax.value_and_grad(
        lambda p: ref.loss_value(p, toks[:, :-1], toks[:, 1:], model))(params)
    return cfg, got, want


@pytest.mark.parametrize("case", list(CASES))
def test_the_loss_matches_the_reference(case):
    """S 80: two chunks of the delta rule, the second padded."""
    cfg, (got, _), (want, _) = _loss_and_gradients(case)
    np.testing.assert_allclose(got, want, rtol=RTOL)
    plan = {"whole_model": [("periods", 2)], "one_period": [("periods", 1)],
            "share": [("periods", 1)], "starts_at_layer_1": [("loose", 4)]}
    assert cfg.plan()[3] == plan[case]


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("case", list(CASES))
def test_every_gradient_leaf_matches_the_reference(case, name):
    _, (_, g_got), (_, g_want) = _loss_and_gradients(case)
    seen = 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree.leaves(g_want)):
        if path[-1].key != name:
            continue
        seen += 1
        assert bool(jnp.all(jnp.isfinite(a)))
        if name == "router_bias" \
                or name == "router" and "first_expert" in CASES[case]:
            # a share's combine weights are constants of the backward pass,
            # and the bias only ever enters the choice
            assert float(jnp.abs(a).max()) == 0 == float(jnp.abs(b).max())
            continue
        scale = float(jnp.abs(b).max()) + 1e-30
        assert scale > 1e-12, jax.tree_util.keystr(path)
        # the decay's parameters reach the loss through exp(A_log) x
        # softplus(a) inside an exp, summed over every token and channel of
        # a head with both signs: their float32 sums cancel to ~1e-4
        loose = name in ("a_log", "dt_bias", "w_f", "w_f_down")
        np.testing.assert_allclose(a / scale, b / scale,
                                   atol=3e-4 if loose else GRAD_ATOL,
                                   err_msg=jax.tree_util.keystr(path))
    assert seen, name


def test_the_periods_phase():
    """Published layers 0-3 are ONE aligned period (GQA first, a scan over
    its three KDA layers); a held set that starts at layer 1 has no whole
    period and runs loose; with the full layer at phase 2 the periods start
    there and what comes before runs loose."""
    cfg, params, _ = _model(**PERIOD)
    assert cfg.plan() == ([], [], [0], [("periods", 1)])
    assert [i for i in range(8) if cfg.is_full(i)] == [0, 4]
    assert params["periods"]["gqa"]["wq"].shape[0] == 1
    assert params["periods"]["kda"]["wq"].shape[:2] == (1, 3)
    assert params["periods"]["gqa"]["w_attn_gate"].shape[1:] == (64, 4, 16)
    loose = _model(layers=(1, 2, 3, 4))[0]
    assert loose.plan() == ([], [1, 2, 3, 4], [], [("loose", 4)])
    assert layer_pattern.segments(tuple(range(8)), 0, 4, 2) == (
        [], [0, 1, 6, 7], [2], [("loose", 2), ("periods", 1), ("loose", 2)])
    before = device_profiler.snapshot()["counters"]
    jax.jit(lambda p, t: solar_open2.forward_hidden(p, t, cfg)[0]).lower(
        params, _tokens(0)[:, :-1])
    after = device_profiler.snapshot()["counters"]
    moved = {k: after[k] - before.get(k, 0) for k in after
             if after[k] != before.get(k, 0)}
    assert moved["pattern.periods"] == 1 and moved["kda.layers"] == 1
    assert "pattern.layers_unrolled" not in moved


def test_a_scanned_period_equals_the_same_layers_unrolled():
    cfg, params, model = _model()
    toks = _tokens(3)[:, :-1]
    with jax.default_matmul_precision("highest"):
        got, chosen = solar_open2.forward_hidden(params, toks, cfg)
        x = params["embed"][toks]
        positions = jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)
        for n, (i, p) in enumerate(ref.layer_params(params, model)):
            x, e = solar_open2.layer(x, p, positions, cfg, None, None,
                                     full=cfg.is_full(i))
            np.testing.assert_array_equal(e, chosen[n])
        x = blocks.rms_norm(x, params["final_norm"], cfg.norm_eps)
    np.testing.assert_allclose(got, x, rtol=RTOL, atol=ATOL)


def test_remat_changes_nothing():
    cfg, params, _ = _model(**PERIOD)
    toks = _tokens(4)
    loss = lambda c: jax.value_and_grad(  # noqa: E731
        lambda p: solar_open2.loss_fn(p, {"tokens": toks}, c))(params)
    plain, g_plain = loss(cfg)
    for policy in ("dots", "residuals"):
        again, g_again = loss(dataclasses.replace(
            cfg, remat=True, remat_policy=policy))
        np.testing.assert_allclose(again, plain, rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g_again), jax.tree.leaves(g_plain)):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_param_axes_match_the_parameters():
    for over in ({}, PERIOD, dict(layers=(1, 2, 3, 4))):
        cfg, params, model = _model(**over)
        is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
        axes = solar_open2.param_logical_axes(cfg)
        assert jax.tree.structure(params) == jax.tree.structure(
            axes, is_leaf=is_axes)
        for a, w in zip(jax.tree.leaves(axes, is_leaf=is_axes),
                        jax.tree.leaves(params)):
            assert len(a) == w.ndim
        assert opcount_solar2.num_params(model) == cfg.num_params() \
            == sum(a.size for a in jax.tree.leaves(params))


def test_the_published_count_of_parameters():
    """The cell's share, by kind, to the figures ISSUE 64 reckoned with."""
    cfg = solar_open2.SolarOpen2Config(
        vocab_size=24_576, layers=(0, 1, 2, 3), n_experts_held=10)
    assert solar_open2.gqa_num_params(cfg) == 109_051_904
    from ray_tpu.models import mixers
    assert mixers.kda_num_params(cfg) == 137_732_288
    assert cfg.num_params() == 1_420_916_544
    whole = solar_open2.SolarOpen2Config()
    # 48 layers over 320 experts and the whole vocabulary: ~250 B
    assert 2.4e11 < whole.num_params() < 2.6e11


# --------------------------------------------------------------------------
# the gate a channel
# --------------------------------------------------------------------------

def test_the_channel_gate_is_the_head_gate_where_a_heads_columns_repeat():
    """`blocks.channel_gated` on a W_gate whose [D, H, K] columns are one
    [D, H] column a head, repeated, is `blocks.head_gated`; and
    `attn_sublayer` picks the form by the weight's shape."""
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    attn = jax.random.normal(ks[0], (2, 10, 4, 16))
    h = jax.random.normal(ks[1], (2, 10, 64))
    w_head = jax.random.normal(ks[2], (64, 4)) * 0.2
    w_channel = jnp.repeat(w_head[..., None], 16, axis=-1)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            blocks.channel_gated(attn, h, w_channel),
            blocks.head_gated(attn, h, w_head), rtol=1e-6, atol=1e-7)
        # a gate of its own a channel differs
        other = blocks.channel_gated(
            attn, h, jax.random.normal(ks[3], (64, 4, 16)) * 0.2)
        assert float(jnp.abs(other - blocks.head_gated(attn, h, w_head))
                     .max()) > 1e-2
        cfg, params, model = _model(**PERIOD)
        p = jax.tree.map(lambda a: a[0], params["periods"]["gqa"])
        x = jax.random.normal(ks[0], (2, 24, cfg.d_model))
        positions = jnp.broadcast_to(jnp.arange(24), (2, 24))
        run = lambda p: blocks.attn_sublayer(  # noqa: E731
            x, p, positions, cfg, rotary=blocks.Rotary(theta=0.0))
        by_head = dict(p, w_attn_gate=p["w_attn_gate"][..., 0])
        by_channel = dict(p, w_attn_gate=jnp.repeat(
            by_head["w_attn_gate"][..., None], cfg.d_head, axis=-1))
        np.testing.assert_allclose(run(by_channel), run(by_head), rtol=1e-5,
                                   atol=1e-6)
        # and the reference reads the shape the same way
        for q in (by_head, by_channel, p):
            np.testing.assert_allclose(
                run(q)[0], ref.gqa(x[0], q, model), rtol=RTOL, atol=ATOL)


def test_no_position_enters_the_gqa_layer():
    """NoPE: the sublayer's output at a token depends on the tokens before
    it and not on where the sequence starts: positions shifted by 1,000
    give the same output bit for bit."""
    cfg, params, _ = _model(**PERIOD)
    p = jax.tree.map(lambda a: a[0], params["periods"]["gqa"])
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 24, cfg.d_model))
    positions = jnp.arange(24)[None]
    run = lambda pos: blocks.attn_sublayer(  # noqa: E731
        x, p, pos, cfg, rotary=blocks.Rotary(theta=0.0))
    assert bool(jnp.all(run(positions) == run(positions + 1000)))


# --------------------------------------------------------------------------
# the share
# --------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["gqa", "kda"])
def test_the_shares_add_up_to_the_uncut_layer(kind):
    """The routed parts of the 4 shares (4 experts each of 16, one group)
    plus the shared expert ONCE are the uncut REFERENCE's expert block, of
    a GQA layer and of a KDA layer."""
    cfg, params, model = _model(**PERIOD)
    p = jax.tree.map(lambda a: a[0] if kind == "gqa" else a[0, 1],
                     params["periods"][kind])
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        h = blocks.rms_norm(x, p["mlp_norm"], cfg.norm_eps)
        routed, shared, want_chosen = ref.experts(h[0], p, model)
        want = x[0] + routed + shared
        total, shared_once = x, None
        for first in range(0, 16, 4):
            share = dataclasses.replace(cfg, n_experts_held=4,
                                        first_expert=first)
            part = dict(p, experts=jax.tree.map(
                lambda a: a[first:first + 4], p["experts"]))
            y, s, e = experts.expert_parts(h, part, share)
            np.testing.assert_array_equal(e, want_chosen)
            total, shared_once = total + y, s
        total = total + shared_once
    np.testing.assert_allclose(total[0], want, rtol=RTOL, atol=ATOL)


def test_an_ep_mesh_axis_is_refused():
    cfg, params, _ = _model(**PERIOD)

    class Mesh:
        shape = {"ep": 2}

    p = jax.tree.map(lambda a: a[0], params["periods"]["gqa"])
    with pytest.raises(NotImplementedError):
        experts.expert_sublayer(jnp.zeros((1, 4, cfg.d_model)), p, cfg,
                                mesh=Mesh())


def test_a_config_outside_the_pattern_is_refused():
    with pytest.raises(ValueError):
        solar_open2.SolarOpen2Config.tiny(full_phase=4)
    with pytest.raises(ValueError):
        solar_open2.SolarOpen2Config.tiny(n_kv_heads=3)
    with pytest.raises(ValueError):
        solar_open2.SolarOpen2Config.tiny(layers=(3, 2))
    with pytest.raises(ValueError):
        solar_open2.SolarOpen2Config.tiny(n_experts_held=4, first_expert=14)
