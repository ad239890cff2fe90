"""`models/granite_hybrid.py` (Granite-4.0-H: a Mamba-2 or attention mixer
AND a dense MLP a layer, four published multipliers, a tied head) against
the plain float32 reference `benchmarks/reference_granite4.py`: loss and
every gradient on seeded weights at tiny widths, every multiplier away from
1, two periods, ONE group of 8 Mamba heads, S no multiple of the chunk; and
the controls that have to FAIL the same comparison."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_granite4 as ref
from ray_tpu._private import device_profiler
from ray_tpu.models import blocks
from ray_tpu.models import granite_hybrid as G

# float32 against float32-"highest" (tests/test_nemotron_h_reference.py)
LOSS_RTOL = 2e-5
GRAD_ATOL = 3e-5
# what a control has to miss the reference by, in tolerances (`_miss`)
CONTROL_MISS = 20
MULTIPLIERS = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")


def _fields(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if f.name != "dtype"}


def _tokens(seed=1, rows=2, seq=40):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0,
                              512)


@functools.lru_cache(maxsize=None)
def _case(layers=None):
    cfg = G.GraniteHybridConfig.tiny(dtype=jnp.float32, layers=layers)
    params = G.init(cfg, jax.random.PRNGKey(0))
    # norm scales and D away from 1, so that each is seen; q and k four
    # times as large, so that the softmax is far from flat and the scores'
    # scale is seen
    keys = iter(jax.random.split(jax.random.PRNGKey(9), 64))

    def moved(path, a):
        names = "".join(str(k) for k in path)
        if "norm" in names or "d_skip" in names:
            return a * (1 + 0.2 * jax.random.normal(next(keys), a.shape))
        return a * 4 if "wq" in names or "wk" in names else a

    return cfg, jax.tree_util.tree_map_with_path(moved, params)


def _program(cfg, params, toks):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: G.loss_fn(p, {"tokens": toks}, cfg))(params)


@functools.lru_cache(maxsize=None)
def _reference(layers=None, **over):
    cfg, params = _case(layers)
    toks = _tokens()
    model = dict(_fields(cfg), **over)
    return jax.value_and_grad(lambda p: ref.loss_value(
        p, toks[:, :-1], toks[:, 1:], model))(params)


@functools.lru_cache(maxsize=None)
def _got(layers=None):
    cfg, params = _case(layers)
    return _program(cfg, params, _tokens())


def _leaves(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


_LEAVES = sorted(_leaves(jax.eval_shape(
    lambda: G.init(G.GraniteHybridConfig.tiny(), jax.random.PRNGKey(0)))))


def test_the_loss_matches_the_reference():
    assert float(_got()[0]) == pytest.approx(float(_reference()[0]),
                                             rel=LOSS_RTOL)


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_matches_the_reference(leaf):
    got, want = _leaves(_got()[1])[leaf], _leaves(_reference()[1])[leaf]
    assert bool(jnp.all(jnp.isfinite(got)))
    scale = float(jnp.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got / scale, want / scale, atol=GRAD_ATOL)


def test_loose_layers_match_the_reference_too():
    """Layers 1-7 of 8: three unrolled before the one aligned period."""
    held = (1, 2, 3, 4, 5, 6, 7)
    cfg, _ = _case(held)
    assert cfg.plan() == ([1, 2, 3], [4], [("loose", 3), ("periods", 1)])
    assert float(_got(held)[0]) == pytest.approx(
        float(_reference(held)[0]), rel=LOSS_RTOL)
    got, want = _leaves(_got(held)[1]), _leaves(_reference(held)[1])
    for leaf in got:
        scale = float(jnp.abs(want[leaf]).max())
        np.testing.assert_allclose(got[leaf] / scale, want[leaf] / scale,
                                   atol=GRAD_ATOL)


def _miss(cfg):
    """How far the program under `cfg` is from the reference under the
    published reading, in TOLERANCES: the loss's relative error over
    `LOSS_RTOL` or a gradient leaf's (of its largest entry) over
    `GRAD_ATOL`, whichever is worse; under 1 is a match. (At these sizes
    attention moves the loss little; the gradient of its own weights
    shows a wrong scale at once.)"""
    _, params = _case()
    loss, grads = _program(cfg, params, _tokens())
    want, want_grads = _reference()
    worst = abs(float(loss) - float(want)) / abs(float(want)) / LOSS_RTOL
    for got, ref_leaf in zip(jax.tree.leaves(grads),
                             jax.tree.leaves(want_grads)):
        scale = float(jnp.abs(ref_leaf).max())
        worst = max(worst, float(jnp.abs(got - ref_leaf).max()) / scale
                    / GRAD_ATOL)
    return worst


@pytest.mark.parametrize("how", ["dropped", "twice"])
@pytest.mark.parametrize("name", MULTIPLIERS)
def test_a_multiplier_dropped_or_applied_twice_misses_the_reference(name, how):
    cfg, _ = _case()
    value = getattr(cfg, name)
    assert value != 1.0
    wrong = dataclasses.replace(
        cfg, **{name: 1.0 if how == "dropped" else value * value})
    assert _miss(cfg) < 1
    assert _miss(wrong) > CONTROL_MISS


@pytest.mark.parametrize("control", ["rope", "scale_of_sqrt_d"])
def test_a_rotary_embedding_or_the_usual_scale_misses_the_reference(control):
    cfg, _ = _case()
    wrong = dataclasses.replace(cfg, rope_theta=10000.0) if control == "rope" \
        else dataclasses.replace(cfg, attention_multiplier=cfg.d_head ** -0.5)
    assert _miss(wrong) > CONTROL_MISS


def test_a_gated_norm_over_each_heads_channels_misses_the_program():
    """The reference with the gated norm over 16-channel groups (a head's)
    in place of ONE over all 128 is another function: the program, whose
    norm is over all of a group's channels, is not near it."""
    cfg, _ = _case()
    by_head = float(_reference(gate_norm_groups=cfg.mamba_heads)[0])
    got = float(_got()[0])
    assert abs(got - by_head) / abs(by_head) > CONTROL_MISS * LOSS_RTOL


def test_the_tied_embeddings_gradient_is_the_sum_of_its_two_uses():
    """The program's d loss / d E against an UNTIED copy of itself: the
    lookup's part plus the head's part, transposed back. Either alone is not
    the reference's gradient."""
    cfg, params = _case()
    toks = _tokens()
    inputs, targets = toks[:, :-1], toks[:, 1:]

    def untied(embed, head):
        hidden = G.forward_hidden(dict(params, embed=embed), inputs, cfg)
        hidden = blocks.scaled(hidden, 1.0 / cfg.logits_scaling)
        return blocks.chunked_ce(hidden, head, targets, chunk=inputs.shape[1])

    with jax.default_matmul_precision("highest"):
        lookup, head = jax.grad(untied, argnums=(0, 1))(
            params["embed"], params["embed"].T)
    tied = _got()[1]["embed"]
    scale = float(jnp.abs(tied).max())
    np.testing.assert_allclose((lookup + head.T) / scale, tied / scale,
                               atol=1e-6)
    want = _reference()[1]["embed"]
    for part in (lookup, head.T):
        assert float(jnp.abs(part - want).max()) / scale > 100 * GRAD_ATOL


def test_scanned_periods_equal_the_same_layers_as_one_period():
    """Two periods of four under the outer scan, and the same eight layers
    as ONE period of eight (runs of 2, 1, 3, 1, 1): the same function of
    the same weights, restacked."""
    cfg, params = _case()
    one = dataclasses.replace(cfg, period=8)
    assert [n for _, n in one.runs()] == [2, 1, 3, 1, 1]
    restacked = dict(params, periods=jax.tree.map(
        lambda a: a.reshape((1, -1) + a.shape[2:]), params["periods"]))
    toks = _tokens()
    with jax.default_matmul_precision("highest"):
        a = G.loss_fn(params, {"tokens": toks}, cfg)
        b = G.loss_fn(restacked, {"tokens": toks}, one)
    assert float(a) == pytest.approx(float(b), rel=1e-6)


@pytest.mark.parametrize("policy", ["full", "dots", "residuals"])
def test_remat_changes_nothing(policy):
    cfg, params = _case()
    toks = _tokens()
    plain = dataclasses.replace(cfg, remat=False)
    under = dataclasses.replace(cfg, remat_policy=policy)
    grad = lambda c: jax.grad(  # noqa: E731
        lambda p: G.loss_fn(p, {"tokens": toks}, c))(params)
    for a, b in zip(jax.tree.leaves(grad(plain)), jax.tree.leaves(grad(under))):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_an_unknown_remat_policy_is_refused():
    cfg, params = _case()
    with pytest.raises(ValueError, match="residuals"):
        G.loss_fn(params, {"tokens": _tokens()},
                  dataclasses.replace(cfg, remat_policy="some"))


def test_lowering_counts_layers_by_kind_and_the_scans_chunk():
    """Two kinds of layer body are traced whatever the depth: a period's
    runs of `mamba` layers share one trace (`jax.checkpoint` keeps it by
    the body and its shapes), its attention layer has the other."""
    cfg, params = _case()
    before = device_profiler.snapshot()["counters"]
    jax.jit(lambda p, t: G.loss_fn(p, {"tokens": t}, cfg)).lower(
        params, _tokens())
    after = device_profiler.snapshot()["counters"]
    moved = {k: after[k] - before.get(k, 0) for k in (
        "granite.layers_mamba", "granite.layers_attention", "ssd.calls",
        "ssd.chunks", "pattern.periods")}
    assert moved == {"granite.layers_mamba": 1, "granite.layers_attention": 1,
                     "ssd.calls": 1, "ssd.chunks": 3, "pattern.periods": 2}


def test_param_axes_match_the_parameters():
    for layers in (None, (1, 2, 3, 4, 5, 6, 7)):
        cfg = G.GraniteHybridConfig.tiny(layers=layers)
        shapes = jax.eval_shape(lambda: G.init(cfg, jax.random.PRNGKey(0)))
        axes = G.param_logical_axes(cfg)
        flat = _leaves(shapes)
        named = {jax.tree_util.keystr(k): v for k, v in
                 jax.tree_util.tree_leaves_with_path(
                     axes, is_leaf=lambda x: isinstance(x, tuple))}
        assert sorted(flat) == sorted(named)
        for key, shape in flat.items():
            assert len(named[key]) == len(shape.shape), key
        assert sum(int(np.prod(a.shape)) for a in flat.values()) \
            == cfg.num_params()


def test_the_published_count_of_parameters():
    """ISSUE 55's table: a mamba layer 76,182,976, an attention layer
    60,821,504, a period of ten 746,468,288, the embedding 205,520,896 once
    (it is the head): 40 layers 3,191,396,096; the ten held 951,991,232."""
    c = G.GraniteHybridConfig()
    assert G.layer_num_params(c, "mamba") == 25_847_232 + 50_331_648 + 4_096 \
        == 76_182_976
    assert G.layer_num_params(c, "attention") \
        == 10_485_760 + 50_331_648 + 4_096 == 60_821_504
    period = sum(G.layer_num_params(c, k) for k in c.pattern[:10])
    assert period == 746_468_288
    embedding = c.vocab_size * c.d_model
    assert embedding == 205_520_896
    assert sum(G.layer_num_params(c, k) for k in c.pattern) + embedding \
        + c.d_model == c.num_params() == 3_191_396_096
    held = G.GraniteHybridConfig(layers=tuple(range(10)))
    assert held.num_params() == 951_991_232
    assert c.pattern == G.PUBLISHED_PATTERN and len(c.pattern) == 40
    assert [i for i, k in enumerate(c.pattern) if k == "attention"] \
        == [5, 15, 25, 35]


def test_an_untied_head_and_a_pattern_that_does_not_repeat_are_refused():
    with pytest.raises(NotImplementedError, match="embedding"):
        G.GraniteHybridConfig.tiny(tie_word_embeddings=False)
    with pytest.raises(ValueError, match="repeat"):
        G.GraniteHybridConfig.tiny(
            pattern=("mamba", "attention", "mamba", "mamba") * 2, period=3)


def test_the_embedding_starts_the_residual_at_unit_rms():
    c = G.GraniteHybridConfig.tiny(vocab_size=4096)
    embed = G.init(c, jax.random.PRNGKey(3))["embed"].astype(jnp.float32)
    rms = float(jnp.sqrt(jnp.mean(jnp.square(c.embedding_multiplier * embed))))
    assert rms == pytest.approx(1.0, rel=0.02)
