"""The pattern model (`models/hybrid_moe.py`: KDA linear-attention and gated
MLA layers to a period, experts chosen within groups) over `ops/kda.py`,
`models/mla_moe.py` and `parallel/moe.py`, against the token-by-token
recurrence and the plain reference `benchmarks/reference_ling.py`, at tiny
sizes on the CPU, seeded weights. The program runs in float32 here, so that
routing cannot flip between the two: every difference is summation order.
"""

import dataclasses
import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_ling as ref
from ray_tpu._private import device_profiler
from ray_tpu.models import blocks, experts, hybrid_moe, mixers, mla_moe
from ray_tpu.ops import kda as kda_op
from ray_tpu.parallel import moe
from tools import kda_chip_check

# float32 against float32-"highest" (see tests/test_mla_moe_reference.py);
# the chunked delta rule sums a chunk in another order than the recurrence
RTOL = ATOL = 2e-5
# a gradient leaf of the model: the chunked form's solve (ten products of
# 64 x 64) and its sub-block factors (e^+-40 apart, multiplied back
# together) lose a few bits more than a plain sum: up to 2.7e-5 measured; a
# bfloat16 matmul anywhere (4e-3 a product) is still 60x over it
GRAD_ATOL = 6e-5

# --------------------------------------------------------------------------
# ops/kda.py against the recurrence
# --------------------------------------------------------------------------

KDA_TENSORS = ("o", "state", "dq", "dk", "dv", "dg", "dbeta")
# g near 0 (a state that forgets nothing over the sequence) and g = -5
# THROUGHOUT: a chunk's cumulative decay is then e^-320, which no float32
# holds: anything divided by it, or exp() of a positive difference, is inf
# aligned keys: every key within ~23 degrees of one direction, beta ~0.98, g
# near 0, past where ten training steps take a layer (cos 0.3-0.5): A is
# then near the all-ones strictly lower matrix, whose powers reach 4.5e17
# before they vanish. A solve that multiplies the chunk's powers together
# reads o off by 1e20 of itself here, or NaN (the cell's loss did, PERF.md
# section 6, PR 39)
REGIMES = {"g_near_0": (-0.05, 0.0, False), "g_minus_5": (-5.0, 30.0, False),
           "aligned_keys": (-0.05, 0.0, True)}
# what only the ANY-DECAY plan takes (`kda`'s `g_min` None). g_minus_60: g a
# channel its own, from ~-0.01 to -60 a step (the fastest channels' decay
# over a chunk is e^-3840: any division by it or any positive exponent is
# inf or NaN; the bounded plan reads NaN here). beta_1999: the aligned keys
# with beta 1.999 throughout, I - beta k k^T with an eigenvalue of -0.999
ANY_REGIMES = {**REGIMES, "g_minus_60": (-60.0, None, False),
               "beta_1999": (-0.05, 0.0, True)}
# sha256 of the bounded `jnp` form's primitives in order, taken on the commit
# before the any-decay plan (PR 63's tree)
BOUNDED_JAXPR_DIGEST = "dc3960939f371186"
PLANS = {"bounded": REGIMES, "any_decay": ANY_REGIMES}
CASES = [(plan, regime) for plan, regimes in PLANS.items()
         for regime in regimes]


def _everything(fn, args, w):
    """fn(*args) -> (o, state): them and the five gradients of
    sum(o * w) + sum(state), by `KDA_TENSORS`' names."""
    def scalar(*a):
        o, state = fn(*a)
        return jnp.sum(o * w) + jnp.sum(state), (o, state)
    grads, (o, state) = jax.grad(
        scalar, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    return dict(zip(KDA_TENSORS, (o, state) + grads))


def _interpreted(*a, plan="bounded"):
    return kda_op._kda(*a, False, True, plan == "bounded")


@functools.lru_cache(maxsize=None)
def _kda_case(regime, s, plan="bounded"):
    low, shift, aligned = ANY_REGIMES[regime]
    ks = jax.random.split(jax.random.PRNGKey(7), 7)
    b, h, d = 2, 2, 32
    l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    k = jax.random.normal(ks[1], (b, h, s, d))
    if aligned:
        k = jax.random.normal(ks[6], (b, h, 1, d)) + 0.4 * k
    args = (
        l2(jax.random.normal(ks[0], (b, h, s, d))) * d ** -0.5,
        l2(k),
        jax.random.normal(ks[2], (b, h, s, d)),
        low * jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, s, d)) + (
            jnp.linspace(-9.0, 4.0, d) if shift is None else shift)),
        jnp.full((b, h, s), 1.999) if regime == "beta_1999"
        else jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, s))
                            + (4.0 if aligned else 0.0)))
    w = jax.random.normal(ks[5], (b, h, s, d))
    with jax.default_matmul_precision("highest"):
        return (_everything(kda_op.kda_recurrence, args, w),
                _everything(lambda *a: kda_op._kda(
                    *a, False, False, plan == "bounded"), args, w),
                args)


@pytest.mark.parametrize("tensor", KDA_TENSORS)
@pytest.mark.parametrize("s", [128, 100], ids=["chunks_whole", "s_100"])
@pytest.mark.parametrize("plan,regime", CASES)
def test_kda_chunked_matches_the_recurrence(plan, regime, s, tensor):
    want, got, args = _kda_case(regime, s, plan)
    if regime == "g_minus_5":
        assert float(args[3].max()) < -4.99
    if regime == "g_minus_60":
        assert float(args[3].min()) < -55 and float(args[3].max()) > -0.1
    if regime == "aligned_keys":
        assert float(jnp.einsum("bhtd,bhid->bhti", args[1], args[1]).min()) \
            > 0.5
    assert bool(jnp.all(jnp.isfinite(got[tensor])))
    scale = float(jnp.abs(want[tensor]).max()) + 1e-30
    # beta 1.999 on aligned keys: the state's component along k changes
    # sign every token and barely decays, so rounding carries further
    # (2.2e-5 of the largest entry measured): the model's gradient bound
    np.testing.assert_allclose(got[tensor] / scale, want[tensor] / scale,
                               atol=GRAD_ATOL if regime == "beta_1999"
                               else ATOL)


@functools.lru_cache(maxsize=None)
def _kda_kernel_case(regime, plan="bounded"):
    want, _, args = _kda_case(regime, 100, plan)
    w = jax.random.normal(jax.random.split(jax.random.PRNGKey(7), 7)[5],
                          args[0].shape)
    with jax.default_matmul_precision("highest"):
        return want, _everything(
            functools.partial(_interpreted, plan=plan), args, w)


@pytest.mark.parametrize("tensor", KDA_TENSORS)
@pytest.mark.parametrize("plan,regime", CASES)
def test_kda_kernel_in_the_interpreter_matches_the_recurrence(plan, regime,
                                                              tensor):
    """The three Pallas kernels (what the TPU runs), interpreted: o and the
    final state from the forward, the five gradients from the backward
    pass's two walks (the state's cotangent enters the last chunk), S no
    multiple of the chunk. dg is a reversed cumulative sum of terms that
    cancel pair by pair: with g = -5 throughout it keeps 2e-5 of its
    largest entry, so it gets the model's gradient tolerance."""
    want, got = _kda_kernel_case(regime, plan)
    assert bool(jnp.all(jnp.isfinite(got[tensor])))
    scale = float(jnp.abs(want[tensor]).max()) + 1e-30
    np.testing.assert_allclose(
        got[tensor] / scale, want[tensor] / scale,
        atol=GRAD_ATOL if tensor == "dg" or regime == "beta_1999" else ATOL)


def _kda_counters_of(lower):
    before = device_profiler.snapshot()["counters"]
    lower()
    return {k: v - before.get(k, 0)
            for k, v in device_profiler.snapshot()["counters"].items()
            if k.startswith("kda.") and v != before.get(k, 0)}


def test_kda_counts_its_chunks():
    """Per lowering: the chunks a sequence, and of the solve the C x C
    products a kernel's grid step stands for (10 a row) beside the products
    it issues: half where the rows go in lane-packed pairs (b x h 4: 4
    rows a step), all of them at 3 (one row a step) and in the `jnp` form."""
    args = _kda_case("g_near_0", 100)[2]
    bounded = functools.partial(kda_op.kda, g_min=kda_op.G_MIN_BOUNDED)
    assert _kda_counters_of(lambda: jax.jit(bounded).lower(*args)) == {
        "kda.chunks": 2, "kda.solve_products": 80,
        "kda.solve_passes_packed": 80}
    # the any-decay plan in the `jnp` form: six score products a chunk for
    # A's rows and six for B's, 2 x 2 x 2 chunks as rows
    assert _kda_counters_of(lambda: jax.jit(kda_op.kda).lower(*args)) == {
        "kda.chunks": 2, "kda.solve_products": 80,
        "kda.solve_passes_packed": 80, "kda.halving_products": 96}
    for heads, products, issued in ((4, 40, 20), (3, 10, 10)):
        args = kda_chip_check.inputs(
            "mixed", jax.random.PRNGKey(0), b=1, h=heads, s=100, d=32)[0]
        # the kernels' traces are cached by shape: another test's lowering
        # of this one would leave nothing to count
        kda_op._kda_fwd_pallas.clear_cache()
        assert _kda_counters_of(lambda: jax.jit(functools.partial(
            bounded, interpret=True)).lower(*args)) == {
                "kda.chunks": 2, "kda.solve_products": products,
                "kda.solve_passes_packed": issued, "kda.kernels": 1}, heads
        # the same kernel under the any-decay plan: counted as such, and
        # 6 levels x (A's rows + B's) products a row of the grid step
        kda_op._kda_fwd_pallas.clear_cache()
        assert _kda_counters_of(lambda: jax.jit(functools.partial(
            kda_op.kda, interpret=True)).lower(*args)) == {
                "kda.chunks": 2, "kda.solve_products": products,
                "kda.solve_passes_packed": issued, "kda.kernels": 1,
                "kda.kernels_any_decay": 1,
                "kda.halving_products": 12 * (4 if heads == 4 else 1)}, heads


def test_the_bounded_plan_fails_where_only_the_any_decay_plan_holds():
    """The control: `g_minus_60` through the bounded plan (a caller's
    breach of the bound it gave: nothing looks, as the docstring says)
    reads inf or NaN; `kda` picks the any-decay plan for any bound below
    -5 and for none."""
    _, _, args = _kda_case("g_minus_60", 128, "any_decay")
    o = kda_op.kda(*args, g_min=kda_op.G_MIN_BOUNDED)
    assert not bool(jnp.all(jnp.isfinite(o)))
    assert bool(jnp.all(jnp.isfinite(kda_op.kda(*args, g_min=-60.0))))
    assert [kda_op.plan_is_bounded(g) for g in (None, -60.0, -5.01, -5.0,
                                                 -1.0)] \
        == [False, False, False, True, True]


def test_the_bounded_plans_jaxpr_is_pinned():
    """The bounded plan's `jnp` form and its custom_vjp, as a digest of
    the jaxpr's equations (primitive names in order): what Ling's model
    ran before the any-decay plan came. A change to the bounded plan moves
    it; `tools/step_lowering_hash.py` is the same proof for the kernels."""
    import hashlib

    args = _kda_case("g_near_0", 128)[2]
    jaxpr = jax.make_jaxpr(lambda *a: kda_op._kda_chunked(*a, True))(*args)

    def names(j):
        out = []
        for e in j.eqns:
            out.append(e.primitive.name)
            for v in e.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        out += names(inner)
        return out

    digest = hashlib.sha256(" ".join(names(jaxpr.jaxpr)).encode())
    assert digest.hexdigest()[:16] == BOUNDED_JAXPR_DIGEST


# --------------------------------------------------------------------------
# the solve on lane-packed pairs of rows: the same arithmetic
# --------------------------------------------------------------------------

def test_a_pair_of_products_in_one_is_exact():
    """[X1 | X2] . diag(Y1, Y2) == [X1 Y1 | X2 Y2] for float32 `highest`:
    what the diagonal weight adds to each sum is exact zeros."""
    kx, ky = jax.random.split(jax.random.PRNGKey(3))
    c = kda_op.CHUNK
    x = jax.random.normal(kx, (3, c, 2 * c)) * 1e3
    y = jax.random.normal(ky, (3, c, 2 * c))
    mm = kda_op._mm_in(jnp.float32)
    exact = lambda a, b: mm(a, b, 1, 0, exact=True)  # noqa: E731
    want = jnp.concatenate([exact(x[..., :c], y[..., :c]),
                            exact(x[..., c:], y[..., c:])], axis=2)
    got = kda_op._pair_products(x, y, exact)
    assert got.shape == want.shape and bool(jnp.all(got == want))
    # and `==` can tell: operands rounded to bf16 give another product
    assert not bool(jnp.all(kda_op._mm_in(jnp.bfloat16)(
        x[..., :c], y[..., :c], 1, 0) == want[..., :c]))


@functools.lru_cache(maxsize=None)
def _packed_and_unpacked(kind, heads):
    """The interpreted kernels on the tool's input `kind` at b x h =
    `heads`, S no multiple of the chunk -> (o, the final state and the five
    gradients as the tree has them, the same with the tool's
    `unpacked_solve()`: every row's solve its own C x C products)."""
    args, w = kda_chip_check.inputs(
        kind, jax.random.PRNGKey(5), b=1, h=heads, s=150, d=32,
        dtype=jnp.float32)
    got = _everything(_interpreted, args, w)
    with kda_chip_check.unpacked_solve():
        return got, _everything(_interpreted, args, w)


@pytest.mark.parametrize("tensor", KDA_TENSORS)
@pytest.mark.parametrize("heads", [4, 8, 3])
@pytest.mark.parametrize("kind", ["mixed", "aligned_keys"])
def test_the_packed_solve_equals_the_unpacked_bit_for_bit(kind, heads, tensor):
    """4 and 8 rows go through the solve in pairs (a grid step of two
    pairs, of four), 3 rows one row a step as ever: o, the final state and
    all five gradients EQUAL the unpacked solve's, not merely close."""
    got, want = _packed_and_unpacked(kind, heads)
    assert bool(jnp.all(jnp.isfinite(got[tensor])))
    assert float(jnp.abs(got[tensor]).max()) > 0
    assert bool(jnp.all(got[tensor] == want[tensor]))


@functools.lru_cache(maxsize=None)
def _long_memory_case(how):
    """`tools/kda_chip_check.py`'s `long_memory` input (a state written in
    the first chunk that every later chunk changes by under half a bf16
    ulp), float32 operands, 2,048 tokens -> the tool's errors of o and the
    gradients against the recurrence, for the float32 state and for its
    control, the same code with the state rounded to bf16 between chunks."""
    args, w = kda_chip_check.inputs(
        "long_memory", jax.random.PRNGKey(11), b=1, h=2, s=2048, d=32,
        dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        return kda_chip_check.compare(args, w, interpret=how == "kernel")


@pytest.mark.parametrize("how", ["kernel", "jnp"])
def test_kda_keeps_its_state_in_float32(how):
    """What a bf16 state cannot pass: over 31 chunks of slow decay the
    float32 state has lost ~4.5% where a rounded one stands still. The
    control is the tool's `bf16_state()`, which rounds the KERNELS' state;
    the `jnp` form has no such control and only has to agree."""
    got = _long_memory_case(how)
    assert max(got["kernel"].values()) < 1e-4, got["kernel"]
    if how == "kernel":
        assert got["bf16_state"]["o"] > 1e-2, got["bf16_state"]


def test_the_chip_check_refuses_a_backend_that_is_no_tpu(monkeypatch, capsys):
    monkeypatch.setattr("sys.argv", ["kda_chip_check.py"])
    assert kda_chip_check.main() == 3
    assert "not a TPU" in capsys.readouterr().err


# --------------------------------------------------------------------------
# the model against the reference
# --------------------------------------------------------------------------

CUT = dict(layers=(1, 3, 4, 5))       # a dense layer + one period of 3
SHARE = dict(n_experts_held=4, first_expert=4)


def _model(seed=0, **over):
    cfg = hybrid_moe.HybridMoeConfig.tiny(
        vocab_size=256, dtype=jnp.float32, remat=False, loss_chunk_size=16,
        **over)
    params = hybrid_moe.init(cfg, jax.random.PRNGKey(seed))
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


def _tokens(seed, rows=2, seq=24):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0, 256)


def _assert_loss_and_gradients(cfg, params, model, toks, atol=GRAD_ATOL):
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.jit(jax.value_and_grad(
            lambda p: hybrid_moe.loss_fn(p, {"tokens": toks}, cfg)))(params)
    want, g_want = jax.value_and_grad(
        lambda p: ref.loss_value(p, toks[:, :-1], toks[:, 1:], model))(params)
    np.testing.assert_allclose(got, want, rtol=RTOL)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_got),
                            jax.tree.leaves(g_want)):
        scale = float(jnp.abs(b).max()) + 1e-30
        # the decay's parameters reach the loss through exp(A_log) a inside
        # a sigmoid inside an exp, summed over every token and channel of
        # a head with both signs: their float32 sums cancel to ~1e-4
        loose = path[-1].key in ("a_log", "dt_bias", "w_f")
        np.testing.assert_allclose(a / scale, b / scale,
                                   atol=max(atol, 3e-4) if loose else atol,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize(
    "over", [{**CUT, **SHARE}, CUT, dict(CUT, rope_interleave=False)],
    ids=["share", "whole", "rope_in_halves"])
def test_a_dense_layer_and_a_period_match_the_reference(over):
    cfg, params, model = _model(**over)
    assert cfg.plan()[3] == [("dense", 1), ("periods", 1)]
    _assert_loss_and_gradients(cfg, params, model, _tokens(1))


def test_the_whole_published_pattern_matches_the_reference():
    """42 layers, 2 dense, period 6, as published: layers 2-5 unrolled, six
    scanned periods; every kind of layer at its published index."""
    cfg, params, model = _model(n_layers_published=42, period=6,
                                n_dense_layers=2)
    assert cfg.plan()[3] == [("dense", 2), ("loose", 4), ("periods", 6)]
    assert [i for i in range(42) if cfg.is_mla(i)] == [5, 11, 17, 23, 29, 35,
                                                       41]
    assert params["periods"]["kda"]["wq"].shape[:2] == (6, 5)
    assert params["loose"]["mla"]["wq"].shape[0] == 1
    # every sublayer's output projection at 1 / sqrt(2 x 42) of its draw, as
    # a model this deep is initialised (GPT-2's rule). At the plain draw each
    # branch is as large as the stream it joins and 42 of them amplify a
    # float32 rounding ~1e4-fold: the gradient then reads 1e-4 to 2e-3 over
    # eight seeds of the tokens, by the order XLA happens to give the sums
    # (PERF.md section 6, PR 44). Scaled, 0.8e-5 to 5.4e-5 over the same
    # seeds, and a layer at the wrong index, or of the wrong kind, still
    # moves whole leaves by O(1): two neighbouring KDA layers swapped read
    # 3.3e-3 in the loss and over 1e-4 in 99 of the 103 leaves, up to 0.97
    params = jax.tree_util.tree_map_with_path(
        lambda path, w: w * (2 * 42) ** -0.5
        if path[-1].key in ("wo", "w_down") else w, params)
    _assert_loss_and_gradients(cfg, params, model, _tokens(2, rows=1, seq=10),
                               atol=2e-4)


def test_scanned_periods_equal_the_same_layers_unrolled():
    cfg, params, model = _model(layers=tuple(range(2, 12)))
    assert cfg.plan()[3] == [("loose", 1), ("periods", 3)]
    toks = _tokens(3)[:, :-1]
    with jax.default_matmul_precision("highest"):
        got, chosen = hybrid_moe.forward_hidden(params, toks, cfg)
        x = params["embed"][toks]
        positions = jnp.broadcast_to(jnp.arange(toks.shape[1]), toks.shape)
        for n, (i, p) in enumerate(ref.layer_params(params, model)):
            x, e = hybrid_moe.layer(x, p, positions, cfg, None, None,
                                     mla=cfg.is_mla(i), dense=False)
            np.testing.assert_array_equal(e, chosen[n])
        x = blocks.rms_norm(x, params["final_norm"], cfg.norm_eps)
    np.testing.assert_allclose(got, x, rtol=RTOL, atol=ATOL)


def test_remat_changes_nothing():
    cfg, params, _ = _model(**CUT, **SHARE)
    toks = _tokens(5)
    grads = lambda c: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda p: hybrid_moe.loss_fn(p, {"tokens": toks}, c)))(params)
    plain, remat = grads(cfg), grads(dataclasses.replace(cfg, remat=True))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(remat)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_lowering_counts_layers_by_kind():
    cfg, params, _ = _model(**CUT)
    before = device_profiler.snapshot()["counters"]
    jax.jit(lambda p, t: hybrid_moe.forward_hidden(p, t, cfg)[0]).lower(
        params, _tokens(0)[:, :-1])
    after = device_profiler.snapshot()["counters"]
    grew = lambda k: after.get(k, 0) - before.get(k, 0)  # noqa: E731
    # layer BODIES: the dense KDA layer, the period's scanned KDA body
    # (traced once for its two layers) and its MLA layer
    assert (grew("kda.layers"), grew("mla.layers")) == (2, 1)
    assert grew("pattern.periods") == 1 and grew("moe.groups_kept") == 4


def test_param_axes_match_the_parameters():
    for over in (CUT, dict(n_layers_published=42, period=6)):
        cfg, params, _ = _model(**over)
        axes = hybrid_moe.param_logical_axes(cfg)
        is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
        assert jax.tree.structure(params) == jax.tree.structure(
            axes, is_leaf=is_axes)
        for a, spec in zip(jax.tree.leaves(params),
                           jax.tree.leaves(axes, is_leaf=is_axes)):
            assert a.ndim == len(spec)


@pytest.mark.parametrize("over", [
    dict(kda_lower_bound=-8.0), dict(kda_lower_bound=None),
    dict(kda_lower_bound=0.0), dict(kda_gate_rank=8),
    dict(kda_beta_scale=2.0)], ids=lambda over: next(iter(over)))
def test_a_kda_gate_outside_lings_published_form_is_refused(over):
    """The KDA sublayer is `mixers.py`'s and takes a gate without a bound,
    low-rank gates and beta in (0, 2) (`solar_open2.py` runs them, against
    `reference_solar2`); this module's published configurations have the
    bounded full-rank form alone, `reference_ling` likewise, so the config
    refuses the others and says where they live. -5 itself stands, and is
    `ops/kda.py`'s bounded plan."""
    with pytest.raises(ValueError, match="solar_open2"):
        hybrid_moe.HybridMoeConfig.tiny(**over)
    assert kda_op.plan_is_bounded(
        hybrid_moe.HybridMoeConfig.tiny(kda_lower_bound=-5.0).kda_lower_bound)


def test_a_nonzero_swiglu_limit_in_a_held_layer_raises():
    limits = (0,) * 8 + (4,) * 4
    hybrid_moe.HybridMoeConfig.tiny(layers=(1, 3, 4, 5),
                                    expert_swiglu_limits=limits)
    with pytest.raises(NotImplementedError, match="expert_swiglu_limits"):
        hybrid_moe.HybridMoeConfig.tiny(expert_swiglu_limits=limits)
    with pytest.raises(NotImplementedError, match="shared_swiglu_limits"):
        hybrid_moe.HybridMoeConfig.tiny(layers=(1, 9, 10, 11),
                                        shared_swiglu_limits=limits)


def test_an_ep_mesh_axis_is_refused():
    cfg, params, _ = _model(**CUT)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("ep",))
    with pytest.raises(NotImplementedError, match="ep"):
        hybrid_moe.forward_hidden(params, _tokens(0)[:, :-1], cfg, mesh)


def test_the_published_count_of_parameters():
    """The cell's configuration: ISSUE 39's table, to the parameter."""
    cfg = hybrid_moe.HybridMoeConfig(
        vocab_size=19_648, layers=(1, 6, 7, 8, 9, 10, 11), n_experts_held=16)
    kda = 63_049_888 + 2 * 2560
    mla = 31_966_080 + 2 * 2560
    routed = 2560 * 512 + 512 + 17 * 5_898_240
    assert mixers.kda_num_params(cfg) == 63_049_888
    assert mixers.mla_num_params(cfg) == 31_966_080
    assert cfg.num_params() == (
        2 * 19_648 * 2560 + 2560 + kda + 3 * 2560 * 6144
        + 5 * (kda + routed) + mla + routed)
    shapes = jax.eval_shape(lambda: hybrid_moe.init(cfg, jax.random.PRNGKey(0)))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == cfg.num_params()
    # six of the cell's seven layers mix with KDA: one dense layer, then a
    # whole period that runs as the scan
    assert [cfg.is_mla(i) for i in cfg.held_layers] == [False] * 6 + [True]
    assert cfg.plan() == ([1], [], [6], [("dense", 1), ("periods", 1)])


# --------------------------------------------------------------------------
# the choice within groups
# --------------------------------------------------------------------------

def _router(seed, t=64, d=32, e=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (t, d)),
            jax.random.normal(ks[1], (d, e)) * 0.5,
            jax.random.normal(ks[2], (e,)) * 0.2)


@pytest.mark.parametrize("groups", [(4, 2), (8, 4), (2, 1)])
def test_group_limited_choice_matches_the_reference(groups):
    n_group, topk_group = groups
    x, w, bias = _router(5)
    model = dict(experts_per_token=4, n_group=n_group, topk_group=topk_group)
    with jax.default_matmul_precision("highest"):
        got = moe.route(x, w, 4, True, score="sigmoid", bias=bias, scale=2.5,
                        n_group=n_group, topk_group=topk_group)
        dense_w, idx = ref.route(x, {"router": w, "router_bias": bias}, model)
    np.testing.assert_array_equal(np.sort(got.experts, -1), np.sort(idx, -1))
    # every choice lies in one of the kept groups, and at most topk_group
    assert int(jnp.max(jnp.sum(jnp.any(
        (got.experts // (16 // n_group))[..., None] == jnp.arange(n_group),
        axis=1), axis=-1))) <= topk_group
    placed = jnp.sum(jax.nn.one_hot(got.experts, 16)
                     * got.weights[..., None], 1)
    np.testing.assert_allclose(placed, dense_w, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("bias_on", [True, False], ids=["bias", "no_bias"])
def test_one_group_is_the_ungrouped_choice_bit_for_bit(bias_on):
    """`n_group` 1 (JoyAI's file) is the route there was."""
    x, w, bias = _router(6)
    bias = bias if bias_on else None
    got = moe.route(x, w, 4, True, score="sigmoid", bias=bias, scale=2.5,
                    n_group=1, topk_group=1)
    probs = jax.nn.sigmoid(jnp.dot(
        x, w, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32))
    if bias_on:
        _, experts = jax.lax.top_k(probs + bias, 4)
        weights = jnp.take_along_axis(probs, experts, -1)
    else:
        weights, experts = jax.lax.top_k(probs, 4)
    weights = weights / jnp.sum(weights, -1, keepdims=True) * 2.5
    np.testing.assert_array_equal(got.experts, experts)
    np.testing.assert_array_equal(got.weights, weights)
    lowered = lambda **kw: jax.jit(lambda x, w, b: moe.route(  # noqa: E731
        x, w, 4, True, score="sigmoid", bias=b, scale=2.5, **kw)).lower(
            x, w, bias).as_text()
    assert lowered() == lowered(n_group=1, topk_group=1)


def test_a_group_limit_that_cannot_hold_the_choice_is_refused():
    x, w, bias = _router(7)
    with pytest.raises(ValueError, match="groups"):
        moe.route(x, w, 8, score="sigmoid", bias=bias, n_group=8, topk_group=2)


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of the 4 shares (4 experts each of 16, chosen
    within groups) plus the shared expert ONCE are the uncut layer."""
    cfg, params, _ = _model(**CUT)
    p = jax.tree.map(lambda a: a[0, 0], params["periods"]["kda"])
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, cfg.d_model))
    h = blocks.rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    shared = (jax.nn.silu(h @ p["shared"]["w_gate"])
              * (h @ p["shared"]["w_up"])) @ p["shared"]["w_down"]
    with jax.default_matmul_precision("highest"):
        whole, chosen = experts.expert_sublayer(x, p, cfg)
        total = x + shared
        for first in range(0, 16, 4):
            share = dataclasses.replace(cfg, n_experts_held=4,
                                        first_expert=first)
            part = dict(p, experts=jax.tree.map(
                lambda a: a[first:first + 4], p["experts"]))
            y, e = experts.expert_sublayer(x, part, share)
            np.testing.assert_array_equal(e, chosen)
            total = total + (y - x - shared)
    np.testing.assert_allclose(total, whole, rtol=RTOL, atol=ATOL)


# --------------------------------------------------------------------------
# what `mla_moe` gained, one at a time
# --------------------------------------------------------------------------

@pytest.mark.parametrize("over", [
    dict(q_lora_rank=0), dict(qk_head_norm=True), dict(attn_gate=True),
    dict(n_group=4, topk_group=2)], ids=lambda o: next(iter(o)))
def test_mla_moe_options_keep_parameters_and_axes_in_step(over):
    cfg = mla_moe.MlaMoeConfig.tiny(vocab_size=256, dtype=jnp.float32,
                                    remat=False, **over)
    params = mla_moe.init(cfg, jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    is_axes = lambda x: isinstance(x, tuple)  # noqa: E731
    assert jax.tree.structure(params) == jax.tree.structure(
        mla_moe.param_logical_axes(cfg), is_leaf=is_axes)
    toks = _tokens(4)
    loss, grads = jax.value_and_grad(
        lambda p: mla_moe.loss_fn(p, {"tokens": toks}, cfg))(params)
    assert bool(jnp.isfinite(loss))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
