"""The Nemotron-H model (`models/nemotron_h.py`: Mamba-2 layers, experts in a
latent, GQA attention, one sublayer a layer, an MTP block) over `ops/ssd.py`
and `parallel/moe.py`, against the token-by-token recurrence and the plain
reference `benchmarks/reference_nemotron3.py`, at tiny sizes on the CPU,
seeded weights. The program runs in float32 here, so that routing cannot
flip between the two: every difference is summation order.
"""

import dataclasses
import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_nemotron3 as ref
from ray_tpu._private import device_profiler
from ray_tpu.models import blocks, nemotron_h
from ray_tpu.ops import ssd as ssd_op
from ray_tpu.parallel import moe
from tools import ssd_chip_check

# float32 against float32-"highest" (see tests/test_mla_moe_reference.py);
# the chunked scan sums a chunk in another order than the recurrence
RTOL = ATOL = 2e-5
# a gradient leaf of the model: up to 5.1e-6 of its largest entry measured;
# a bfloat16 matmul anywhere (4e-3 a product) is 100x over it
GRAD_ATOL = 3e-5

# --------------------------------------------------------------------------
# ops/ssd.py against the recurrence
# --------------------------------------------------------------------------

SSD_TENSORS = ("y", "state", "dx", "ddelta", "da", "db", "dc")
# the decay a token: none at all (a = 0: a state that only adds up), what a
# layer has at initialisation (exp(-0.002) to exp(-2)), and STRONG: a chunk's
# cumulative log decay reaches -3,000, whose exponential no float32 holds:
# anything divided by it, or exp() of a positive difference, is inf or NaN
REGIMES = {"no_decay": 0.0, "as_seeded": 1.0, "strong": 30.0}


def _everything(fn, args, w):
    """fn(*args) -> (y, state): them and the five gradients of
    sum(y * w) + sum(state), by `SSD_TENSORS`' names."""
    def scalar(*a):
        y, state = fn(*a)
        return jnp.sum(y * w) + jnp.sum(state), (y, state)
    grads, (y, state) = jax.grad(
        scalar, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    return dict(zip(SSD_TENSORS, (y, state) + grads))


def _ssd_args(regime, s, b=2, h=4, g=2, p=16, n=8):
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 2.0)
    a = -REGIMES[regime] * jnp.exp(jax.random.normal(ks[2], (h,))) * delta
    return (jax.random.normal(ks[0], (b, s, h, p)), delta, a,
            jax.random.normal(ks[3], (b, s, g, n)),
            jax.random.normal(ks[4], (b, s, g, n))), \
        jax.random.normal(ks[5], (b, s, h, p))


@functools.lru_cache(maxsize=None)
def _ssd_case(regime, s, how):
    args, w = _ssd_args(regime, s)
    fn = {"recurrence": ssd_op.ssd_recurrence,
          "jnp": lambda *a: ssd_op._ssd(*a, False, False),
          "kernel": lambda *a: ssd_op._ssd(*a, False, True)}[how]
    with jax.default_matmul_precision("highest"):
        return _everything(fn, args, w)


def _assert_close(got, want, atol):
    assert bool(jnp.all(jnp.isfinite(got)))
    scale = float(jnp.abs(want).max()) + 1e-30
    np.testing.assert_allclose(got / scale, want / scale, atol=atol)


@pytest.mark.parametrize("tensor", SSD_TENSORS)
@pytest.mark.parametrize("s", [256, 200], ids=["chunks_whole", "s_200"])
@pytest.mark.parametrize("regime", list(REGIMES))
def test_ssd_chunked_matches_the_recurrence(regime, s, tensor):
    """With the strong decay the running sums reach -3,000 and their
    differences keep ~1e-4 of themselves: 2.2e-5 of a tensor measured."""
    _assert_close(_ssd_case(regime, s, "jnp")[tensor],
                  _ssd_case(regime, s, "recurrence")[tensor],
                  5e-5 if regime == "strong" else ATOL)


@pytest.mark.parametrize("tensor", SSD_TENSORS)
@pytest.mark.parametrize("regime", list(REGIMES))
def test_ssd_kernel_in_the_interpreter_matches_the_recurrence(regime, tensor):
    """The three Pallas kernels (what the TPU runs), interpreted: y and the
    final state from the forward, the five gradients from the backward
    pass's two walks (the state's cotangent enters the last chunk), S no
    multiple of the chunk."""
    _assert_close(_ssd_case(regime, 200, "kernel")[tensor],
                  _ssd_case(regime, 200, "recurrence")[tensor],
                  5e-5 if regime == "strong" else ATOL)


@pytest.mark.parametrize("heads", [(4, 1, 8), (6, 3, 64), (2, 2, 128)],
                         ids=["four_a_block", "pairs", "one_a_block"])
def test_ssd_kernel_at_other_head_layouts(heads):
    """Heads side by side in a 128-lane block: four of P 8 (one group),
    two of P 64 (the cell's), one of P 128."""
    h, g, p = heads
    args, w = _ssd_args("as_seeded", 130, b=1, h=h, g=g, p=p)
    with jax.default_matmul_precision("highest"):
        want = _everything(ssd_op.ssd_recurrence, args, w)
        got = _everything(lambda *a: ssd_op._ssd(*a, False, True), args, w)
    for tensor in SSD_TENSORS:
        _assert_close(got[tensor], want[tensor], ATOL)


@functools.lru_cache(maxsize=None)
def _layer_scan_grads(how):
    """`ssd` from the layer's parameters -> gradients of dt, A_log, D and
    dt_bias (and x), against the recurrence under the same wrapping."""
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    b, s, h, g, p, n = 2, 150, 4, 2, 16, 8
    args = (jax.random.normal(ks[0], (b, s, h, p)),
            jax.random.normal(ks[1], (b, s, h)) - 2.0,
            jnp.log(jax.random.uniform(ks[2], (h,), minval=1., maxval=16.)),
            jax.random.normal(ks[3], (b, s, g, n)),
            jax.random.normal(ks[4], (b, s, g, n)),
            1.0 + 0.3 * jax.random.normal(ks[5], (h,)),
            jax.random.normal(ks[6], (h,)))
    w = jax.random.normal(ks[7], (b, s, h, p))

    def by_recurrence(x, dt, a_log, bm, cm, d_skip, dt_bias):
        delta = jax.nn.softplus(dt + dt_bias)
        y, _ = ssd_op.ssd_recurrence(x, delta, -jnp.exp(a_log) * delta, bm, cm)
        return y + d_skip[:, None] * x

    fn = by_recurrence if how == "recurrence" else functools.partial(
        ssd_op.ssd, interpret=how == "kernel", use_pallas=False)
    with jax.default_matmul_precision("highest"):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * w),
                        argnums=tuple(range(7)))(*args)


@pytest.mark.parametrize("leaf", ["x", "dt", "a_log", "b", "c", "d_skip",
                                  "dt_bias"])
@pytest.mark.parametrize("how", ["kernel", "jnp"])
def test_ssd_gradients_of_the_layers_parameters(how, leaf):
    """The decay's parameters reach the loss through exp() of differences
    of running sums that reach -250 here (A up to 16 at a Delta of ~0.13):
    float32 keeps 3e-5 of such a sum, and a head's gradient adds the terms
    of every token: 5.5e-5 of the leaf measured."""
    i = ["x", "dt", "a_log", "b", "c", "d_skip", "dt_bias"].index(leaf)
    _assert_close(_layer_scan_grads(how)[i], _layer_scan_grads("recurrence")[i],
                  2e-4 if leaf in ("dt", "a_log", "dt_bias") else ATOL)


def test_ssd_counts_its_calls_and_chunks():
    args, _ = _ssd_args("as_seeded", 200)
    before = device_profiler.snapshot()["counters"]
    jax.jit(lambda *a: ssd_op.ssd_scan(*a)[0]).lower(*args)
    after = device_profiler.snapshot()["counters"]
    assert {k: after[k] - before.get(k, 0)
            for k in ("ssd.calls", "ssd.chunks")} == {
                "ssd.calls": 1, "ssd.chunks": 2}


@functools.lru_cache(maxsize=None)
def _long_memory_case(how):
    """`tools/ssd_chip_check.py`'s `long_memory` input (a state written in
    the first chunk that every later chunk changes by under half a bf16
    ulp), float32 operands, 2,048 tokens -> the tool's errors of y and the
    gradients against the recurrence, for the float32 state and for its
    control, the same code with the state rounded to bf16 between chunks."""
    args, w = ssd_chip_check.inputs(
        "long_memory", jax.random.PRNGKey(11), b=1, s=2048, h=4, p=16, g=2,
        n=8, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        return ssd_chip_check.compare(args, w, interpret=how == "kernel",
                                      use_pallas=False)


@pytest.mark.parametrize("how", ["kernel", "jnp"])
def test_ssd_keeps_its_state_in_float32(how):
    """What a bf16 state cannot pass: over 15 chunks of slow decay the
    float32 state has lost ~2.7% where a rounded one stands still. The
    control is the tool's `bf16_state()`, which rounds the KERNELS' state;
    the `jnp` form has no such control and only has to agree."""
    got = _long_memory_case(how)
    assert max(got["kernel"].values()) < 1e-4, got["kernel"]
    if how == "kernel":
        assert got["bf16_state"]["y"] > 5e-3, got["bf16_state"]


def test_the_chip_check_refuses_a_backend_that_is_no_tpu(monkeypatch, capsys):
    monkeypatch.setattr("sys.argv", ["ssd_chip_check.py"])
    assert ssd_chip_check.main() == 3
    assert "not a TPU" in capsys.readouterr().err


# --------------------------------------------------------------------------
# the model against the reference
# --------------------------------------------------------------------------

# `tiny`'s pattern is M*EMEMEM*EME: 1-7 are a `*` and three (E, M) pairs
CUT = dict(layers=tuple(range(1, 8)))
SHARE = dict(n_experts_held=4, first_expert=4)


def _model(seed=0, **over):
    cfg = nemotron_h.NemotronHConfig.tiny(
        vocab_size=256, dtype=jnp.float32, remat=False, loss_chunk_size=16,
        **over)
    params = nemotron_h.init(cfg, jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 100)

    def rescale(path, w):
        name = path[-1].key
        sub = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % 2**31)
        if name.endswith("norm") or name == "d_skip":
            return (1.0 + 0.3 * jax.random.normal(sub, w.shape)).astype(w.dtype)
        if name == "router_bias":
            return 0.2 * jax.random.normal(sub, w.shape)
        return w

    params = jax.tree_util.tree_map_with_path(rescale, params)
    return cfg, params, dataclasses.asdict(cfg)


def _tokens(seed, rows=2, seq=24):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0, 256)


def _assert_loss_and_gradients(cfg, params, model, toks, atol=GRAD_ATOL):
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(
            lambda p: nemotron_h.loss_fn(p, {"tokens": toks}, cfg)))(params)
    want, g_want = jax.value_and_grad(
        lambda p: ref.loss_value(p, toks[:, :-1], toks[:, 1:], model))(params)
    np.testing.assert_allclose(got, want, rtol=RTOL)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree.leaves(g_want)):
        scale = float(jnp.abs(b).max()) + 1e-30
        np.testing.assert_allclose(a / scale, b / scale, atol=atol,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize(
    "over", [{**CUT, **SHARE}, CUT, dict(CUT, mtp_depth=0),
             {**CUT, **SHARE, "mtp_depth": 0}, {}],
    ids=["share", "whole", "no_mtp", "share_no_mtp", "all_twelve_layers"])
def test_the_model_matches_the_reference(over):
    """Loss and every gradient leaf, with and without the MTP block, a
    share and every expert; `all_twelve_layers` also runs each kind
    unrolled (a trailing E, M, E that fill no two pairs)."""
    cfg, params, model = _model(**over)
    if "layers" in over:
        assert cfg.plan() == [("one", "*", 1), ("pairs", 2, 3)]
    _assert_loss_and_gradients(cfg, params, model, _tokens(1))


def test_the_whole_published_pattern_matches_the_reference():
    """88 layers in the published order: a leading M, `*` and three to five
    scanned (E, M) pairs eight times over, a trailing E. The LOSS (the
    gradients are `test_the_model_matches_the_reference`'s): a layer of the
    wrong kind, or at the wrong index, moves it by O(1)."""
    cfg, params, model = _model(pattern=nemotron_h.PUBLISHED_PATTERN)
    plan = cfg.plan()
    assert [s[2] for s in plan if s[0] == "pairs"] == [3, 4, 4, 5, 5, 5, 5,
                                                       4, 4]
    assert [s[1:] for s in plan if s[0] == "one"] == [("M", 0)] + [
        ("*", i) for i in (7, 16, 25, 36, 47, 58, 69, 78)] + [("E", 87)]
    assert plan[6:8] == [("one", "*", 25), ("pairs", 26, 5)]
    assert {k: cfg.pattern.count(k) for k in "ME*"} == {"M": 40, "E": 40,
                                                        "*": 8}
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    toks = _tokens(2, rows=1, seq=10)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: nemotron_h.loss_fn(
            p, {"tokens": toks}, cfg))(params)
    np.testing.assert_allclose(
        got, ref.loss_value(params, toks[:, :-1], toks[:, 1:], model),
        rtol=RTOL)


def test_scanned_pairs_equal_the_same_layers_unrolled():
    cfg, params, model = _model(**CUT)
    toks = _tokens(3)[:, :-1]
    with jax.default_matmul_precision("highest"):
        got, chosen = nemotron_h.forward_hidden(params, toks, cfg)
        x = params["embed"][toks]
        positions = jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)
        n = 0
        for _, kind, p in ref.layer_params(params, model):
            x, e = nemotron_h.layer(x, p, positions, cfg, None, None, kind)
            if e is not None:
                np.testing.assert_array_equal(e, chosen[n])
                n += 1
        x = blocks.rms_norm(x, params["final_norm"], cfg.norm_eps)
    assert n == 3
    np.testing.assert_allclose(got, x, rtol=RTOL, atol=ATOL)


def test_remat_changes_nothing():
    cfg, params, _ = _model(**CUT, **SHARE)
    toks = _tokens(5)
    grads = lambda c: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda p: nemotron_h.loss_fn(p, {"tokens": toks}, c)))(params)
    plain, remat = grads(cfg), grads(dataclasses.replace(cfg, remat=True))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(remat)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_lowering_counts_layers_by_kind():
    cfg, params, _ = _model(**CUT, **SHARE)
    toks = _tokens(0)
    before = device_profiler.snapshot()["counters"]
    jax.jit(lambda p, t: nemotron_h.loss_fn(p, {"tokens": t}, cfg)).lower(
        params, toks)
    after = device_profiler.snapshot()["counters"]
    grew = lambda k: after.get(k, 0) - before.get(k, 0)  # noqa: E731
    # layer BODIES: the scanned pair's two (traced once for three pairs),
    # the unrolled `*`, and the MTP block's `*` and `E`
    assert (grew("ssd.layers"), grew("ssd.calls"), grew("ssd.chunks")) == (
        1, 1, 1)
    assert (grew("pattern.periods"), grew("pattern.layers_unrolled")) == (3, 1)
    assert grew("mtp.depth") == 1
    t_k = 2 * 24 * cfg.experts_per_token
    assert grew("moe.latent_rows") == 2 * t_k
    assert grew("moe.gmm_calls") == 2 * 2 and grew("moe.experts_held") == 8
    assert grew("moe.counts_by_comparison") == 2  # once a routed block


def test_param_axes_match_the_parameters():
    for over in (CUT, {}, dict(mtp_depth=0)):
        cfg, params, _ = _model(**over)
        axes = nemotron_h.param_logical_axes(cfg)
        is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
        assert jax.tree.structure(params) == jax.tree.structure(
            axes, is_leaf=is_axes)
        for a, spec in zip(jax.tree.leaves(params),
                           jax.tree.leaves(axes, is_leaf=is_axes)):
            assert a.ndim == len(spec)


def test_an_ep_mesh_axis_is_refused():
    cfg, params, _ = _model(**CUT)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("ep",))
    with pytest.raises(NotImplementedError, match="ep"):
        nemotron_h.forward_hidden(params, _tokens(0)[:, :-1], cfg, mesh)


def test_a_chunk_other_than_the_kernels_is_refused():
    with pytest.raises(ValueError, match="chunks of 128"):
        nemotron_h.NemotronHConfig.tiny(chunk_size=64)


def test_the_published_count_of_parameters():
    """The cell's configuration: ISSUE 43's table, to the parameter."""
    cfg = nemotron_h.NemotronHConfig(
        vocab_size=16_384, layers=tuple(range(25, 36)), n_experts_held=8)
    d = 4096
    mamba = (d * 18_560 + 5 * 10_240 + 3 * 128 + 8_192 + 8_192 * d) + d
    experts = (d * 512 + 512 + 2 * d * 1024 + 2 * d * 5376
               + 8 * 2 * 1024 * 2688) + d
    attn = (2 * d * 4096 + 2 * d * 256) + d
    assert (mamba, experts, attn) == (109_640_064, 98_570_752, 35_655_680)
    period = attn + 5 * experts + 5 * mamba
    mtp = 2 * d * d + 3 * d + attn + experts
    assert (period, mtp) == (1_076_709_760, 167_793_152)
    assert cfg.num_params() == period + 2 * 16_384 * d + d + mtp \
        == 1_378_724_736
    shapes = jax.eval_shape(lambda: nemotron_h.init(cfg, jax.random.PRNGKey(0)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == cfg.num_params()
    assert cfg.pattern[25:36] == "*EMEMEMEMEM"
    assert cfg.plan() == [("one", "*", 25), ("pairs", 26, 5)]


# --------------------------------------------------------------------------
# experts in a latent, of the two-matrix relu^2 form
# --------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of the 4 shares (4 experts each of 16), each brought
    back from the latent, plus the shared expert ONCE are the uncut layer."""
    cfg, params, _ = _model(**CUT)
    p = jax.tree.map(lambda a: a[0], params["pairs"]["experts"])
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, cfg.d_model))
    h = blocks.rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    shared = jnp.square(jax.nn.relu(h @ p["shared"]["w_up"])) \
        @ p["shared"]["w_down"]
    with jax.default_matmul_precision("highest"):
        whole, chosen = nemotron_h.expert_sublayer(x, p, cfg)
        total = x + shared
        for first in range(0, 16, 4):
            share = dataclasses.replace(cfg, n_experts_held=4,
                                        first_expert=first)
            part = dict(p, experts=jax.tree.map(
                lambda a: a[first:first + 4], p["experts"]))
            y, e = nemotron_h.expert_sublayer(x, part, share)
            np.testing.assert_array_equal(e, chosen)
            total = total + (y - x - shared)
    np.testing.assert_allclose(total, whole, rtol=RTOL, atol=ATOL)


def _experts_case(seed=5, t=64, d=32, lat=16, f=24, e=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(ks[0], (t, d)),
            jax.random.normal(ks[1], (d, e)) * 0.5,
            {"w_up": jax.random.normal(ks[2], (e, lat, f)) * lat ** -0.5,
             "w_down": jax.random.normal(ks[3], (e, f, lat)) * f ** -0.5},
            jax.random.normal(ks[4], (t, lat)),
            jax.random.normal(ks[5], (e,)) * 0.2)


@pytest.mark.parametrize("held", [None, (4, 8)], ids=["whole", "share"])
def test_relu2_experts_on_latent_rows_match_a_dense_sum(held):
    """`moe_layer(form="relu2", rows=latent)`: the router reads x, the
    experts the latent rows; against every expert applied to every token."""
    x, router, experts, rows, bias = _experts_case()
    first, n_held = held or (0, 16)
    mine = jax.tree.map(lambda a: a[first:first + n_held], experts)

    def dense(rows, mine):
        routing = moe.route(x, router, 4, True, score="sigmoid", bias=bias,
                            scale=5.0)
        w = jnp.sum(jax.nn.one_hot(routing.experts, 16)
                    * routing.weights[..., None], 1)
        if held:
            w = jax.lax.stop_gradient(w)
        return sum(w[:, first + i:first + i + 1]
                   * (jnp.square(jax.nn.relu(rows @ mine["w_up"][i]))
                      @ mine["w_down"][i]) for i in range(n_held))

    def layer(rows, mine):
        return moe.moe_layer(x, router, mine, 4, True, score="sigmoid",
                             router_bias=bias, weight_scale=5.0, held=held,
                             form="relu2", rows=rows)[0]

    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(layer(*a))), argnums=(0, 1))(rows, mine)
        want, g_want = jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(dense(*a))), argnums=(0, 1))(rows, mine)
    np.testing.assert_allclose(got, want, rtol=RTOL)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=ATOL)


@pytest.mark.parametrize("held", [None, (4, 8)], ids=["whole", "share"])
def test_the_swiglu_lowering_is_the_one_there_was(held):
    """The SwiGLU cells (OLMoE, JoyAI, SDAR, Ling) call `moe_layer` without
    the new arguments: their program is, token for token, the one the
    defaults spelled out give, and dispatching `rows=x` is dispatching x."""
    x, router, _, _, bias = _experts_case()
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    n_held = held[1] if held else 16
    experts = {"w_gate": jax.random.normal(ks[0], (n_held, 32, 24)),
               "w_up": jax.random.normal(ks[1], (n_held, 32, 24)),
               "w_down": jax.random.normal(ks[2], (n_held, 24, 32))}
    lowered = lambda **kw: jax.jit(jax.grad(  # noqa: E731
        lambda x, e: jnp.sum(moe.moe_layer(
            x, router, e, 4, True, score="sigmoid", router_bias=bias,
            held=held, **kw)[0]), argnums=(0, 1))).lower(x, experts).as_text()
    assert lowered() == lowered(form="swiglu", rows=None)
    with pytest.raises(ValueError, match="expert form"):
        lowered(form="gelu")
    before = device_profiler.snapshot()["counters"].get("moe.latent_rows", 0)
    lowered()
    assert device_profiler.snapshot()["counters"].get(
        "moe.latent_rows", 0) == before


def test_attention_without_a_rotary_embedding():
    """`rope_theta` 0 leaves q and k as projected (`blocks.qkv`); any other
    value turns them."""
    cfg, params, _ = _model(**CUT)
    p = jax.tree.map(lambda a: a[0], params["one"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, cfg.d_model))
    positions = jnp.broadcast_to(jnp.arange(24), (2, 24))
    h = blocks.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q, k, _ = blocks.qkv(x, p, positions, cfg)
    np.testing.assert_array_equal(q, jnp.einsum("bsd,dhk->bshk", h, p["wq"]))
    np.testing.assert_array_equal(k, jnp.einsum("bsd,dhk->bshk", h, p["wk"]))
    turned, _, _ = blocks.qkv(x, p, positions,
                              dataclasses.replace(cfg, rope_theta=1e4))
    assert float(jnp.abs(turned - q).max()) > 0.1
