"""The routed-experts model (`models/mixtral.py` over `parallel/moe.py`)
against the plain reference `benchmarks/reference_olmoe.py`, at tiny sizes
on the CPU, seeded weights. The program runs in float32 here unless a test
says otherwise, so that routing cannot flip between the two: every
difference is then summation order.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_olmoe as ref
from ray_tpu.models import mixtral
from ray_tpu.ops import grouped_matmul as gm
from ray_tpu.parallel import moe

# float32 against float32-"highest": the two differ by the order of ~1e2
# additions per output (d_model 64, 8 experts, 2 layers) of O(1) terms,
# each rounded to 6e-8: 1e-6 to 4e-6 measured over the cases below. 2e-5 is
# 5x that; a bfloat16 matmul anywhere (4e-3 a product) is 200x over it.
RTOL = ATOL = 2e-5

OLMOE = dict(n_heads=4, n_kv_heads=4, n_experts=8, experts_per_token=4,
             norm_topk_prob=False, qk_norm=True, router_z_loss_coef=0.001)
MIXTRAL = dict(n_heads=4, n_kv_heads=2, n_experts=4, experts_per_token=2,
               norm_topk_prob=True, qk_norm=False)
FIELDS = ("n_layers", "n_heads", "n_kv_heads", "d_head", "norm_eps",
          "rope_theta", "experts_per_token", "norm_topk_prob", "qk_norm",
          "aux_loss_coef", "router_z_loss_coef")


def _model(switches, dtype=jnp.float32, seed=0):
    cfg = mixtral.MixtralConfig(
        vocab_size=256, d_model=64, n_layers=2, d_head=16, d_ff=32,
        max_seq_len=64, dtype=dtype, remat=False, **switches)
    params = mixtral.init(cfg, jax.random.PRNGKey(seed))
    # norm scales that are not 1, so that a scale applied in the wrong
    # place or to the wrong channels shows
    k = jax.random.PRNGKey(seed + 100)
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        if name in params["layers"]:
            w = params["layers"][name]
            k, sub = jax.random.split(k)
            params["layers"][name] = (
                1.0 + 0.3 * jax.random.normal(sub, w.shape)).astype(w.dtype)
    return cfg, params, {f: getattr(cfg, f) for f in FIELDS}


def _tokens(seed, rows=2, seq=32):
    t = jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0, 256)
    return t[:, :-1], t[:, 1:]


@pytest.mark.parametrize("switches", [OLMOE, MIXTRAL], ids=["olmoe", "mixtral"])
def test_logits_match_reference(switches):
    cfg, params, model = _model(switches)
    inputs, _ = _tokens(1)
    got, _ = mixtral.forward(params, inputs, cfg)
    want = jnp.stack([ref.logits(params, row, model) for row in inputs])
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("chunk", [0, 16], ids=["full_ce", "chunked_ce"])
@pytest.mark.parametrize("switches", [OLMOE, MIXTRAL], ids=["olmoe", "mixtral"])
def test_loss_matches_reference(switches, chunk):
    cfg, params, model = _model(switches)
    cfg = dataclasses.replace(cfg, loss_chunk_size=chunk)
    inputs, targets = _tokens(2, rows=3)
    got = mixtral.loss_fn(params, {"inputs": inputs, "targets": targets}, cfg)
    ce, lb, rz = ref.loss_terms(params, inputs, targets, model)
    # each term by itself (in the sum, 0.01 x LB is 80x the tolerance)
    _, aux = mixtral.forward_hidden(params, inputs, cfg)
    np.testing.assert_allclose(jnp.mean(aux.load_balance), lb, rtol=RTOL)
    np.testing.assert_allclose(jnp.mean(aux.router_z), rz, rtol=RTOL)
    np.testing.assert_allclose(
        float(got), ref.loss(params, inputs, targets, model), rtol=RTOL)
    # the CE is masked, the batch statistics are not (the harness masks
    # the CE to its reference rows and gives the reference every row)
    mask = jnp.zeros(inputs.shape, jnp.float32).at[:2].set(1.0)
    got = mixtral.loss_fn(
        params, {"inputs": inputs, "targets": targets, "mask": mask}, cfg)
    ce2, _, _ = ref.loss_terms(params, inputs[:2], targets[:2], model)
    want = (float(ce2) + cfg.aux_loss_coef * float(lb)
            + cfg.router_z_loss_coef * float(rz))
    np.testing.assert_allclose(float(got), want, rtol=RTOL)


def test_gradients_match_reference():
    cfg, params, model = _model(OLMOE)
    inputs, targets = _tokens(3)
    got = jax.grad(mixtral.loss_fn)(
        params, {"inputs": inputs, "targets": targets}, cfg)
    want = jax.grad(ref.loss_value)(params, inputs, targets, model)
    # one expert's w_down, the router, the q-norm scale; then everything
    leaves = {
        "w_down[1, 3]": lambda g: g["layers"]["experts"]["w_down"][1, 3],
        "router": lambda g: g["layers"]["moe_gate"],
        "q_norm": lambda g: g["layers"]["q_norm"],
    }
    for name, pick in leaves.items():
        g, w = pick(got), pick(want)
        assert float(jnp.abs(w).max()) > 0, name
        # gradients are sums over 64 tokens of products of O(1e-2) terms:
        # absolute error scales with the leaf's own size
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-5 * float(jnp.abs(w).max()),
            err_msg=name)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=1e-3, atol=1e-4 * float(jnp.abs(w).max()) + 1e-9)


def test_dropless_under_skew():
    """Tokens drawn from two ids only, so nearly all of them choose the same
    2 of 16 experts: one expert receives > 4x its even share (the
    capacity-bounded dispatch this replaced dropped pairs here, at its
    capacity factor of 1.25) and the result still matches the reference."""
    cfg, params, model = _model(dict(
        n_heads=4, n_kv_heads=4, n_experts=16, experts_per_token=2,
        norm_topk_prob=False, qk_norm=True))
    inputs = jax.random.randint(jax.random.PRNGKey(4), (2, 32), 0, 2) + 17
    got, aux = mixtral.forward(params, inputs, cfg)
    counts = np.bincount(np.asarray(aux.experts[0]).reshape(-1),
                         minlength=cfg.n_experts)
    share = counts.max() * cfg.n_experts / counts.sum()
    assert share > 4.0, f"the fullest expert holds {share:.2f}x its share"
    want = jnp.stack([ref.logits(params, row, model) for row in inputs])
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


@pytest.mark.parametrize("n_experts,k", [(4, 1), (4, 2), (8, 4), (64, 8)])
def test_dispatch_is_a_permutation(n_experts, k):
    t = 96
    logits = jax.random.normal(jax.random.PRNGKey(n_experts + k),
                               (t, n_experts))
    _, experts = jax.lax.top_k(logits, k)
    order, inverse, sizes = moe.sort_by_expert(experts, n_experts)
    assert int(sizes.sum()) == t * k                    # nothing dropped
    np.testing.assert_array_equal(np.sort(order), np.arange(t * k))
    np.testing.assert_array_equal(np.asarray(order)[np.asarray(inverse)],
                                  np.arange(t * k))
    flat = np.asarray(experts).reshape(-1)
    assert np.all(np.diff(flat[np.asarray(order)]) >= 0)  # sorted by expert
    np.testing.assert_array_equal(np.bincount(flat, minlength=n_experts),
                                  sizes)
    # stable: within an expert, pairs keep their order
    for e in range(n_experts):
        assert np.all(np.diff(np.asarray(order)[flat[order] == e]) > 0)


@pytest.mark.parametrize("n_experts,k", [(4, 1), (4, 2), (8, 4), (64, 8)])
def test_permutation_gradients_are_the_inverse_gathers(n_experts, k):
    """The hand-written transposes of the layer's three moves (tokens to
    sorted rows, the k weights to sorted order, sorted rows back to a sum
    per token) against autodiff of the plain gathers (a scatter-add), with
    respect to the rows and to the weights."""
    t, d = 24, 8
    ks = jax.random.split(jax.random.PRNGKey(n_experts + k), 4)
    _, experts = jax.lax.top_k(jax.random.normal(ks[0], (t, n_experts)), k)
    order, inverse, _ = moe.sort_by_expert(experts, n_experts)
    x = jax.random.normal(ks[1], (t, d))
    w = jax.random.normal(ks[2], (t, k))
    m = jax.random.normal(ks[3], (t * k, d))   # stands for the experts

    def ours(x, w):
        rows = moe._permute(x, order, inverse, k)
        w_sorted = moe._reorder(w.reshape(-1), order, inverse)
        return jnp.sum(moe._combine(rows * w_sorted[:, None] * m,
                                    order, inverse, k) ** 2)

    def plain(x, w):
        out = x[order // k] * w.reshape(-1)[order][:, None] * m
        return jnp.sum(jnp.sum(out[inverse].reshape(t, k, d), axis=1) ** 2)

    np.testing.assert_allclose(ours(x, w), plain(x, w), rtol=1e-6)
    grads = jax.grad(ours, argnums=(0, 1))
    for g, want in zip(grads(x, w), jax.grad(plain, argnums=(0, 1))(x, w)):
        assert float(jnp.abs(want).max()) > 0
        np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-5)
    assert "scatter" not in str(jax.make_jaxpr(grads)(x, w))
    assert "scatter" in str(jax.make_jaxpr(
        jax.grad(plain, argnums=(0, 1)))(x, w))


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold (scan
    and remat bodies, custom_vjp calls, pjit)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("switches", [OLMOE, MIXTRAL], ids=["olmoe", "mixtral"])
def test_backward_reruns_neither_down_projection_nor_unpermute(switches):
    """The model's gradient as the cell takes it (layers scanned, each under
    `jax.checkpoint` with the "dots" policy, which saves no grouped matmul's
    result). A layer's body appears once in the forward scan and once in
    the backward scan, so the counts below are per layer. Grouped matmuls:
    3 forward, gate and up recomputed, 6 backward = 11; with the top-k
    weights applied after the down projection their gradient needed its
    output, and the recomputation reran it: 12. Gathers of [T * k, D] rows:
    tokens to sorted rows (forward and recomputed), the k-sum's un-permute,
    and the transposes of both = 5; 6 with the recomputed un-permute."""
    cfg, params, _ = _model(switches)
    cfg = dataclasses.replace(cfg, remat=True, remat_policy="dots")
    inputs, targets = _tokens(6, rows=3)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: mixtral.loss_fn(p, {"inputs": inputs, "targets": targets},
                                  cfg)))(params)
    pair_rows = (inputs.size * cfg.experts_per_token, cfg.d_model)
    eqns = list(_equations(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert sum(n.startswith("ragged_dot") for n in names) == 11
    assert sum(e.primitive.name == "gather"
               and e.outvars[0].aval.shape == pair_rows for e in eqns) == 5
    # no gather of rows or of the sorted weights is transposed by autodiff
    for e in eqns:
        if e.primitive.name.startswith("scatter"):
            assert pair_rows not in [v.aval.shape for v in e.invars], e
            assert e.outvars[0].aval.shape != pair_rows[:1], e
    # a policy's name that is not known is refused, not read as "full"
    with pytest.raises(ValueError, match="dots_attn"):
        mixtral.loss_fn(params, {"inputs": inputs, "targets": targets},
                        dataclasses.replace(cfg, remat_policy="dots_attn"))


@pytest.mark.parametrize("m,k,n,sizes", [
    (512, 256, 128, [100, 0, 156, 256]),    # whole tiles, an empty group
    (300, 128, 256, [7, 200, 93]),          # rows padded up to the row tile
    (40, 128, 128, [40]),                   # fewer rows than one tile
])
def test_tpu_grouped_matmul_kernels_in_interpret_mode(m, k, n, sizes):
    """The TPU path's three Pallas calls (forward, rows' gradient, weights'
    gradient) with `grouped_matmul`'s own tiling, clamping and row padding,
    in the Pallas interpreter, against `lax.ragged_dot` and its autodiff:
    what runs on the CPU and what runs on the chip compute the same."""
    ks = jax.random.split(jax.random.PRNGKey(m), 3)
    lhs = jax.random.normal(ks[0], (m, k))
    rhs = jax.random.normal(ks[1], (len(sizes), k, n)) * k ** -0.5
    grad = jax.random.normal(ks[2], (m, n))
    sizes = jnp.array(sizes, jnp.int32)
    want, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(a, b, sizes), lhs, rhs)
    want_dl, want_dr = vjp(grad)
    tol = dict(rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        gm._gmm(lhs, rhs, sizes, False, interpret=True), want, **tol)
    np.testing.assert_allclose(
        gm._gmm(grad, rhs, sizes, True, interpret=True), want_dl, **tol)
    np.testing.assert_allclose(
        gm._tgmm(lhs, grad, sizes, interpret=True), want_dr, **tol)


def test_bf16_program_against_float32_reference():
    """The model as the cell runs it (bf16 weights and activations, float32
    router, softmax and accumulation) against the float32 reference.

    Where rounding flips a token's choice between two near-equal experts
    that token's logits move by O(1), so the comparison is over the tokens
    whose choices all agree, as a root mean square relative to the logits'
    own (which is 1.0 here). Measured over four seeds: 0.9e-2 to 2.2e-2,
    with 0.2% to 0.8% of the (token, slot) choices flipped. The same
    statistic reads 6.5e-2 to 7.8e-2 when every seventh token loses its
    last slot (a capacity-bounded dispatch that drops) and 7.6e-2 to 9.6e-2
    with the matrices rounded to float8_e4m3, which also flips 3% to 4% of
    the choices. The bounds, 4e-2 and 2%, lie between."""
    cfg, params, model = _model(OLMOE, dtype=jnp.bfloat16)
    inputs, targets = _tokens(5, rows=4)
    got, aux = mixtral.forward(params, inputs, cfg)
    want = jnp.stack([ref.logits(params, row, model) for row in inputs])
    theirs = ref.routing(params, inputs, model)
    # a (token, slot) choice agrees if the reference's expert for it is
    # among the program's k
    same = jnp.any(aux.experts[..., :, None] == theirs[..., None, :], -2)
    flipped = 1.0 - float(jnp.mean(same))
    agree = jnp.all(same, axis=(0, 2)).reshape(inputs.shape)[..., None]
    err = float(jnp.sqrt(jnp.sum((got - want) ** 2 * agree)
                         / (jnp.sum(agree) * want.shape[-1])
                         / jnp.mean(want ** 2)))
    msg = (f"rms logit difference over agreeing tokens / rms logit = "
           f"{err:.3e}; {100 * flipped:.2f}% of (token, slot) choices differ")
    assert err < 4e-2, msg
    assert flipped < 0.02, msg
    # the mean over 128 tokens averages the rounding out: 0.3e-4 to 2.6e-4
    # measured (up to 1.1e-3 with the dropped slots)
    loss = float(mixtral.loss_fn(
        params, {"inputs": inputs, "targets": targets}, cfg))
    want_loss = ref.loss(params, inputs, targets, model)
    assert abs(loss - want_loss) / want_loss < 6e-4, (loss, want_loss, msg)


# What the router counts or picks out of [T, E] it does by comparison against
# arange(E) and a dense reduction: a scatter (a `bincount`, autodiff's
# transpose of `top_k`'s values) serialises on the TPU (PERF.md section 6,
# PR 44).

ROUTERS = {
    "softmax": dict(score="softmax"),
    "sigmoid_bias": dict(score="sigmoid", norm_topk_prob=True,
                         weight_scale=2.5),
    "grouped": dict(score="sigmoid", norm_topk_prob=True, weight_scale=2.5,
                    n_group=4, topk_group=2),
}


@pytest.mark.parametrize("what", ["value", "gradient"])
@pytest.mark.parametrize("router", list(ROUTERS))
@pytest.mark.parametrize("held", [None, (2, 4)], ids=["all_held", "share"])
def test_router_lowers_no_scatter(held, router, what):
    """`moe_layer` and `router_losses`' two terms as a function of (x,
    router_w, experts) alone, as jax lowers it: no `stablehlo.scatter`, in
    the value or in the gradient, whichever way the experts are chosen."""
    t, d, f, e, k = 32, 16, 8, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    n_held = e if held is None else held[1]
    experts = {"w_gate": jax.random.normal(ks[0], (n_held, d, f)) * d ** -0.5,
               "w_up": jax.random.normal(ks[1], (n_held, d, f)) * d ** -0.5,
               "w_down": jax.random.normal(ks[2], (n_held, f, d)) * f ** -0.5}
    x = jax.random.normal(ks[3], (t, d))
    router_w = jax.random.normal(ks[4], (d, e))
    how = dict(ROUTERS[router], held=held)
    if router != "softmax":
        how["router_bias"] = 0.1 * jax.random.normal(ks[5], (e,))

    def terms(x, router_w, experts):
        y, aux = moe.moe_layer(x, router_w, experts, k, **how)
        return jnp.sum(y ** 2) + 0.01 * aux.load_balance + 0.001 * aux.router_z

    fn = terms if what == "value" else jax.grad(terms, argnums=(0, 1, 2))
    text = jax.jit(fn).lower(x, router_w, experts).as_text()
    assert "stablehlo.scatter" not in text
    # the program is there: the choice's sort, the count's compare
    assert "chlo.top_k" in text or "stablehlo.sort" in text
    assert "stablehlo.compare" in text


def _choices(kind, t, k, e):
    if kind == "random":
        logits = jax.random.normal(jax.random.PRNGKey(t + k + e), (t, e))
        return jax.lax.top_k(logits, k)[1]
    if kind == "one_expert":        # every pair sent to ONE expert
        return jnp.full((t, k), e - 2, jnp.int32)
    # experts that get none: the choices come from the odd ones alone
    logits = jax.random.normal(jax.random.PRNGKey(t + k + e), (t, e // 2))
    return 2 * jax.lax.top_k(logits, k)[1] + 1


@pytest.mark.parametrize("kind", ["random", "one_expert", "some_get_none"])
@pytest.mark.parametrize("t,k,e", [(96, 2, 8), (40, 4, 64), (7, 1, 3)])
def test_counts_are_bincounts(t, k, e, kind):
    """`router_losses`' counts and `sort_by_expert`'s group sizes against
    `np.bincount`: int32, exact, summing to T x k."""
    if kind == "some_get_none" and e // 2 < k:
        pytest.skip("fewer odd experts than choices")
    experts = _choices(kind, t, k, e)
    want = np.bincount(np.asarray(experts).reshape(-1), minlength=e)
    if kind != "random":
        assert (want == 0).sum() >= e // 2
    counts = moe._count_by_expert(experts, e)
    _, _, sizes = jax.jit(moe.sort_by_expert, static_argnums=1)(experts, e)
    for got in (counts, sizes):
        assert got.dtype == jnp.int32 and got.shape == (e,)
        np.testing.assert_array_equal(got, want)
        assert int(got.sum()) == t * k


def test_router_losses_over_shards_equal_the_scattered_counts():
    """Under `axis_name` in a `shard_map` over tokens: both terms are what
    the whole batch gives on one device, and the load-balance term is the
    one a `bincount` gives, to the last digit (the counts are integers)."""
    from jax.sharding import Mesh, PartitionSpec as P

    t, d, e, k, shards = 64, 16, 8, 2, 4
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    x = jax.random.normal(ks[0], (t, d))
    router_w = jax.random.normal(ks[1], (d, e))
    mesh = Mesh(np.array(jax.devices()[:shards]), ("ep",))

    def old_losses(routing, axis_name=None):
        counts = jnp.bincount(routing.experts.reshape(-1),
                              length=e).astype(jnp.float32)
        p_mean = jnp.mean(routing.probs, axis=0)
        if axis_name is not None:
            counts = jax.lax.pmean(counts, axis_name)
            p_mean = jax.lax.pmean(p_mean, axis_name)
        return e * jnp.sum(counts / (routing.probs.shape[0] * k) * p_mean)

    def local(x_loc, router_w):
        routing = moe.route(x_loc, router_w, k)
        lb, rz = moe.router_losses(routing, "ep")
        return lb, rz, old_losses(routing, "ep")

    lb, rz, old_lb = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(P("ep"), P()), out_specs=(P(), P(), P()),
        check_vma=False))(x, router_w)
    assert float(lb) == float(old_lb)
    whole = moe.route(x, router_w, k)
    want_lb, want_rz = moe.router_losses(whole)
    np.testing.assert_allclose(lb, want_lb, rtol=1e-6)
    np.testing.assert_allclose(rz, want_rz, rtol=1e-6)
    assert float(want_lb) == float(old_losses(whole))


@pytest.mark.parametrize("norm_topk_prob", [False, True],
                         ids=["as_they_are", "normalised"])
@pytest.mark.parametrize("score", ["softmax", "sigmoid"])
def test_route_weights_are_top_ks_values_and_gradient(score, norm_topk_prob):
    """`route` takes the choice from `top_k` and the weights as a masked sum
    over the experts: p plus exact zeros, and on the way back one
    contribution a position. So the weights and d weights / d logits are
    `==` those of `lax.top_k(probs, k)`'s values and its autodiff (a scatter
    of T x k scalars), op by op on one device."""
    t, e, k = 48, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    logits = 2.0 * jax.random.normal(ks[0], (t, e))
    cotangent = jax.random.normal(ks[1], (t, k))
    eye = jnp.eye(e)       # x @ I under `highest`: the logits themselves

    def ours(logits):
        return moe.route(logits, eye, k, norm_topk_prob, score=score).weights

    def top_ks(logits):
        probs = jax.nn.softmax(logits, axis=-1) if score == "softmax" \
            else jax.nn.sigmoid(logits)
        weights, _ = jax.lax.top_k(probs, k)
        if norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights

    np.testing.assert_array_equal(moe.route(logits, eye, k).logits, logits)
    got, want = ours(logits), top_ks(logits)
    assert got.dtype == want.dtype and bool(jnp.all(got == want))
    grad = lambda fn: jax.grad(  # noqa: E731
        lambda lg: jnp.sum(fn(lg) * cotangent))
    got, want = grad(ours)(logits), grad(top_ks)(logits)
    assert float(jnp.abs(want).max()) > 0
    assert bool(jnp.all(got == want))
    assert "scatter" in str(jax.make_jaxpr(grad(top_ks))(logits))
    assert "scatter" not in str(jax.make_jaxpr(grad(ours))(logits))
    # the barrier that keeps the k weights apart from their sum stands in
    # the value alone: on a cotangent it would keep a share's router
    # backward alive, on zeros (`moe._formed_first`)
    assert str(jax.make_jaxpr(grad(ours))(logits)).count(
        "optimization_barrier") == int(norm_topk_prob)


@pytest.mark.parametrize("switches", [OLMOE, MIXTRAL], ids=["olmoe", "mixtral"])
def test_counters_of_a_lowering(switches):
    """What one lowering of the model's gradient counts (the scanned layers
    lower once): a block that holds every expert counts by comparison at
    two sites, the load-balance term's shares and the dispatch's groups."""
    from ray_tpu._private import device_profiler

    cfg, params, _ = _model(switches)
    inputs, targets = _tokens(8)
    before = dict(device_profiler.snapshot()["counters"])
    jax.jit(jax.grad(lambda p: mixtral.loss_fn(
        p, {"inputs": inputs, "targets": targets}, cfg))).lower(params)
    after = device_profiler.snapshot()["counters"]
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    assert delta["moe.counts_by_comparison"] == 2
    assert delta["moe.rows_routed"] == inputs.size * cfg.experts_per_token
    assert delta["moe.experts"] == cfg.n_experts
    assert delta["moe.gmm_calls"] == 3
    assert delta.get("moe.experts_held", 0) == 0
