"""The latent-attention, routed-experts model (`models/mla_moe.py` over
`parallel/moe.py` and `ops/flash_attention.py`) against the plain reference
`benchmarks/reference_joyai.py`, at tiny sizes on the CPU, seeded weights.
The program runs in float32 here, so that routing cannot flip between the
two: every difference is then summation order.
"""

import dataclasses
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_joyai as ref
from ray_tpu._private import device_profiler
from ray_tpu.models import blocks, experts, mixers, mixtral, mla_moe
from ray_tpu.ops import row_moves, row_sums
from ray_tpu.ops.flash_attention import (
    BlockDiffusion, _clamp_block, _reference_attention, block_schedule,
    flash_attention)
from ray_tpu.parallel import moe

# float32 against float32-"highest": ~1e2 additions per output of O(1)
# terms, each rounded to 6e-8 (1e-6 to 4e-6 measured below). 2e-5 is 5x
# that; a bfloat16 matmul anywhere (4e-3 a product) is 200x over it.
RTOL = ATOL = 2e-5

SHARE = dict(n_experts_held=4, first_expert=4)   # experts 4-7 of 16
WHOLE = dict()                                   # all 16


def _model(over=WHOLE, seed=0, **kw):
    # 1 dense + 1 expert layer + the MTP block: every kind once, cheaply
    cfg = mla_moe.MlaMoeConfig.tiny(
        vocab_size=256, n_layers=2, dtype=jnp.float32, remat=False,
        loss_chunk_size=16, **over, **kw)
    params = mla_moe.init(cfg, jax.random.PRNGKey(seed))
    # norm scales that are not 1, so that a scale applied in the wrong
    # place shows; a router bias large enough to move many choices
    key = jax.random.PRNGKey(seed + 100)

    def rescale(path, w):
        name = path[-1].key
        # crc32, not hash(): the same weights in every process
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


@pytest.mark.parametrize("over", [SHARE, WHOLE], ids=["share", "whole"])
def test_loss_and_gradients_match_reference(over):
    """Share on (or every expert held), MTP on: the loss and every leaf of
    its gradient."""
    cfg, params, model = _model(over)
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()
    toks = _tokens(1)
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(
            lambda p: mla_moe.loss_fn(p, {"tokens": toks}, cfg))(params)
    want, g_want = jax.value_and_grad(
        lambda p: ref.loss_value(p, toks[:, :-1], toks[:, 1:], model))(params)
    np.testing.assert_allclose(got, want, rtol=RTOL)
    flat_got = jax.tree_util.tree_leaves_with_path(g_got)
    for (path, a), b in zip(flat_got, jax.tree.leaves(g_want)):
        scale = float(jnp.abs(b).max()) + 1e-30
        np.testing.assert_allclose(
            a / scale, b / scale, atol=ATOL,
            err_msg=jax.tree_util.keystr(path))
    # the bias steers the choice and is not trained; a share's router is
    # not trained either (its combine weights are constants), the whole
    # model's is
    assert not np.any(g_got["layers"]["router_bias"])
    assert not np.any(g_got["mtp"]["block"]["router_bias"])
    assert bool(np.any(g_got["layers"]["router"])) == (over is WHOLE)


@pytest.mark.parametrize("mtp_depth", [0, 1])
def test_logits_match_reference(mtp_depth):
    cfg, params, model = _model(SHARE, mtp_depth=mtp_depth)
    toks = _tokens(2)[:, :-1]
    with jax.default_matmul_precision("highest"):
        got = mla_moe.forward(params, toks, cfg)
    for row_got, row in zip(got, toks):
        np.testing.assert_allclose(row_got, ref.logits(params, row, model),
                                   rtol=RTOL, atol=ATOL)


def test_eight_shares_and_the_shared_expert_once_make_the_whole_layer():
    """The guide's share test: the routed parts that all the shares give,
    plus what every chip computes alike (the shared expert) counted once,
    equal the uncut reference's expert sublayer."""
    cfg, params, model = _model(WHOLE)
    p = jax.tree.map(lambda a: a[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(3), (64, cfg.d_model))
    want_routed, want_shared, chosen = ref.experts(h, p, model)
    n_shares, per = 8, cfg.n_experts // 8
    total = jnp.zeros_like(h)
    live = 0
    with jax.default_matmul_precision("highest"):
        for i in range(n_shares):
            held = jax.tree.map(lambda a: a[i * per:(i + 1) * per],
                                p["experts"])
            y, aux = moe.moe_layer(
                h, p["router"], held, cfg.experts_per_token,
                cfg.norm_topk_prob, score="sigmoid",
                router_bias=p["router_bias"],
                weight_scale=cfg.routed_scaling_factor, held=(i * per, per))
            np.testing.assert_array_equal(aux.experts, chosen)
            total = total + y
            live += int(np.sum((chosen >= i * per) & (chosen < (i + 1) * per)))
    assert live == chosen.size          # every pair is some share's
    np.testing.assert_allclose(total, want_routed, rtol=RTOL, atol=ATOL)
    # and the whole layer through the program with every expert held
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 64, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got, _ = experts.expert_sublayer(x, p, cfg)
    hn = ref._rms(x[0], p["mlp_norm"], cfg.norm_eps)
    routed, shared, _ = ref.experts(hn, p, model)
    np.testing.assert_allclose(got[0], x[0] + routed + shared,
                               rtol=RTOL, atol=ATOL)


def test_sigmoid_routing_bias_and_normalisation():
    x = jax.random.normal(jax.random.PRNGKey(5), (32, 16))
    w = jax.random.normal(jax.random.PRNGKey(6), (16, 8))
    plain = moe.route(x, w, 3, True, score="sigmoid", scale=2.5)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(x, w, precision="highest")))
    top = np.argsort(-s, axis=-1)[:, :3]
    np.testing.assert_array_equal(plain.experts, top)
    picked = np.take_along_axis(s, top, -1)
    # normalised over ALL the chosen (held here or not), then x 2.5
    np.testing.assert_allclose(
        plain.weights, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(plain.weights.sum(-1), 2.5, rtol=1e-5)
    # a bias moves the choice, not the weights' values; it gets no gradient
    bias = jnp.zeros((8,)).at[7].set(10.0)
    biased = moe.route(x, w, 3, True, score="sigmoid", bias=bias, scale=2.5)
    assert np.all(np.asarray(biased.experts)[:, 0] == 7)
    assert np.any(np.asarray(plain.experts)[:, 0] != 7)
    chosen = np.asarray(biased.experts)
    picked = np.take_along_axis(s, chosen, -1)
    np.testing.assert_allclose(
        biased.weights, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    g_bias, g_w = jax.grad(
        lambda b, w: jnp.sum(moe.route(x, w, 3, True, score="sigmoid",
                                       bias=b).weights ** 2),
        argnums=(0, 1))(bias, w)
    assert not np.any(g_bias) and np.any(g_w)
    with pytest.raises(ValueError):
        moe.route(x, w, 3, score="tanh")


@pytest.mark.parametrize("where", ["all_held", "none_held", "even", "heavy"])
def test_nothing_is_dropped_at_either_extreme(where):
    """Every pair on held experts (the buffers' worst case), none, the even
    share and three times it: the live rows are exactly the pairs whose
    expert is held, each lands in the capacity that holds it, and the
    layer's output and gradients are the reference's with exactly those
    pairs."""
    cfg, params, model = _model(SHARE)
    p = jax.tree.map(lambda a: a[0], params["layers"])
    push = {"all_held": 10.0, "none_held": -10.0, "even": 0.0,
            "heavy": 0.2}[where]
    bias = p["router_bias"].at[4:8].add(push)
    p = dict(p, router_bias=bias)
    t, k = 512, cfg.experts_per_token
    h = jax.random.normal(jax.random.PRNGKey(7), (t, cfg.d_model))
    want, _, chosen = ref.experts(h, p, model)
    held = np.asarray((chosen >= 4) & (chosen < 8))
    live = int(held.sum())
    caps = moe.share_capacities(t, k, 4, cfg.n_experts)
    assert caps == (1024, 2048) and caps[-1] == t * k
    lo, hi = {"all_held": (t * k, t * k), "none_held": (0, 0),
              "even": (1, 1023), "heavy": (1024, t * k - 1)}[where]
    assert lo <= live <= hi, live   # so both capacities are exercised
    order, inverse, group_sizes = moe.sort_held(chosen, 4, 4)
    np.testing.assert_array_equal(np.asarray(order)[np.asarray(inverse)],
                                  np.arange(t * k))
    assert int(group_sizes.sum()) == live
    np.testing.assert_array_equal(
        group_sizes, [np.sum(np.asarray(chosen) == e) for e in range(4, 8)])
    # the first `live` sorted positions are exactly the held pairs
    np.testing.assert_array_equal(np.sort(np.asarray(order)[:live]),
                                  np.flatnonzero(held.reshape(-1)))

    def program(h, experts):
        return moe.moe_layer(
            h, p["router"], experts, k, cfg.norm_topk_prob, score="sigmoid",
            router_bias=bias, weight_scale=cfg.routed_scaling_factor,
            held=(4, 4))[0]

    with jax.default_matmul_precision("highest"):
        got = jax.jit(program)(h, p["experts"])
        g_got = jax.grad(lambda h, e: jnp.sum(program(h, e) ** 2),
                         argnums=(0, 1))(h, p["experts"])
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    g_want = jax.grad(
        lambda h, e: jnp.sum(ref.experts(h, dict(p, experts=e), model)[0] ** 2),
        argnums=(0, 1))(h, p["experts"])
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-4)


def _in_token_order(rows, token, slot, live):
    """`ops/row_sums.py`'s TPU form, in the Pallas interpreter."""
    return row_sums._sum_in_token_order(rows, token, live, slot.shape[0],
                                       interpret=True)


def _share_rows(t, k, e, first, n_held, held_pairs, seed):
    """A share's buffer as `_held_rows` builds it, every capacity that
    holds the live rows: token i chooses `held_pairs[i]` held experts and
    k - held_pairs[i] absent ones. -> [(cap, live, token, slot)]."""
    rng = np.random.default_rng(seed)
    held = np.arange(first, first + n_held)
    absent = np.setdiff1d(np.arange(e), held)
    chosen = np.stack([rng.permutation(np.concatenate([
        rng.choice(held, n, replace=False),
        rng.choice(absent, k - n, replace=False)])) for n in held_pairs])
    order, inverse, group_sizes = moe.sort_held(
        jnp.asarray(chosen, jnp.int32), first, n_held)
    live = int(group_sizes.sum())
    assert live == int(np.sum(held_pairs))
    out = []
    for cap in moe.share_capacities(t, k, n_held, e):
        if cap > live or cap == t * k:
            token = jnp.where(jnp.arange(cap) < live, order[:cap] // k, t)
            slot = jnp.where(inverse < live, inverse, cap - 1).reshape(t, k)
            out.append((cap, live, token, slot))
    return out


# (T, k, E, first, n_held, D): two token tiles and eight row tiles of 256;
# and a share smaller than any tile, so that every tile is clamped
_TILED = (512, 4, 16, 4, 4, 384)
_CLAMPED = (48, 2, 8, 2, 2, 128)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", [_TILED, _CLAMPED], ids=["tiled", "clamped"])
@pytest.mark.parametrize("where", ["all_held", "none_held", "even", "heavy"])
def test_sum_rows_in_token_order_is_the_gather_and_sum(where, shape, dtype):
    """The TPU's form of a share's combine (sort the buffer's token ids,
    one gather of `cap` rows, one-hot^T x rows a token tile through megablox
    `tgmm`), in the Pallas interpreter, against `rows[slot]` summed: at
    every pair / no pair / the even share / three times it held, at every
    capacity that holds the live rows, with a token that has no held pair,
    one that has all k, a token tile no row belongs to, and NaN in the dead
    rows, which neither form may read."""
    t, k, e, first, n_held, d = shape
    assert n_held * 4 == e and k <= n_held     # the even share: k / 4 a token
    held_pairs = {
        "all_held": np.full(t, k),
        "none_held": np.zeros(t, np.int64),
        # 0, 1, .. k held pairs by turns in the FIRST half of the tokens
        # (token 0 none, token k all k), none in the second half: its token
        # tiles have no row
        "even": np.where(np.arange(t) < t // 2, np.arange(t) % (k + 1), 0),
        "heavy": np.full(t, -(-3 * k // 4)),
    }[where]
    cases = _share_rows(t, k, e, first, n_held, held_pairs, seed=3)
    # both capacities where the shape has two and the live rows fit both
    both = where in ("even", "none_held") and shape == _TILED
    assert len(cases) == (2 if both else 1), [c[:2] for c in cases]
    if where == "even":
        assert held_pairs[0] == 0 and held_pairs[k] == k
    for cap, live, token, slot in cases:
        rows = np.array(jax.random.normal(jax.random.PRNGKey(cap), (cap, d)))
        want = jnp.sum(jnp.asarray(
            np.where(np.arange(cap)[:, None] < live, rows, 0.0),
            dtype)[slot].astype(jnp.float32), axis=1).astype(dtype)
        rows[live:] = np.nan
        rows = jnp.asarray(rows, dtype)
        got = _in_token_order(rows, token, slot, jnp.int32(live))
        assert got.shape == (t, d) and got.dtype == dtype
        assert not np.isnan(np.asarray(got, np.float32)).any()
        # float32 sums in another order; bf16: the same sum rounded once
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-6 if dtype == jnp.float32 else 2.0 ** -7, atol=1e-6)
        # and the form every other backend runs
        np.testing.assert_array_equal(
            np.asarray(row_sums.sum_rows_by_token(rows, token, slot, live),
                       np.float32), np.asarray(want, np.float32))


# a buffer of two and a half of `ops/row_moves.py`'s row tiles, so that its
# last tile starts early, over T x k = cap slots; k = 2: a sum of two rows
# is the same in either order, so the two forms agree bit for bit
_MOVE_TILE = row_moves._ROW_TILE
_MOVE_T, _MOVE_K, _MOVE_D = _MOVE_TILE * 5 // 4, 2, 128
_MOVE_CAP = _MOVE_T * _MOVE_K


@pytest.mark.parametrize("form", ["xla", "tpu"])
@pytest.mark.parametrize("live", [0, 1, _MOVE_TILE - 1, _MOVE_TILE,
                                  _MOVE_TILE + 1, _MOVE_CAP - 1])
def test_a_shares_row_moves_visit_live_row_tiles_only(live, form, monkeypatch):
    """`_take_rows` and `_sum_rows` (in token order, the TPU's form in the
    Pallas interpreter), each the other's transpose, at the edges of
    `take_live_rows`' loop over row tiles: no live row, one, a tile less
    one, a tile, a tile and one, all but the dead row that absent pairs
    point at. Result and gradient against the plain `x[token]` and
    `rows[slot]`, bit for bit on the live rows; NaN in the dead rows of
    what `_sum_rows` reads; dead rows of what `_take_rows` makes finite up
    to the last live tile's end, and past it zero in the form every backend
    runs, unwritten in the TPU's (its two Pallas calls in the interpreter,
    where unwritten reads NaN)."""
    assert row_moves.row_tiles(_MOVE_CAP) == 3
    t, k, d, cap = _MOVE_T, _MOVE_K, _MOVE_D, _MOVE_CAP
    monkeypatch.setattr(moe, "sum_rows_by_token", _in_token_order)
    if form == "tpu":
        monkeypatch.setattr(row_moves, "_buffer", partial(
            row_moves._unwritten, interpret=True))
        monkeypatch.setattr(row_moves, "_placed", partial(
            row_moves._copied_in, interpret=True))
    rng = np.random.default_rng(live)
    pairs = rng.choice(t * k, live, replace=False)
    token = jnp.asarray(np.concatenate(
        [pairs // k, np.full(cap - live, t)]), jnp.int32)
    slot = np.full(t * k, cap - 1)
    slot[pairs] = np.arange(live)
    slot = jnp.asarray(slot.reshape(t, k), jnp.int32)
    n = jnp.int32(live)
    bits = lambda a: np.asarray(a).view(np.uint16)  # noqa: E731
    key_x, key_rows = jax.random.split(jax.random.PRNGKey(live))
    x = jax.random.normal(key_x, (t, d), jnp.bfloat16)
    rows = jax.random.normal(key_rows, (cap, d), jnp.bfloat16)
    dirty = rows.at[live:].set(jnp.nan)
    clean = rows.at[live:].set(0)

    # the forward moves, and each one's gradient, which is the other move
    taken, take_vjp = jax.vjp(lambda x: moe._take_rows(x, token, slot, n), x)
    summed, sum_vjp = jax.vjp(
        lambda r: moe._sum_rows(r, token, slot, n), dirty)
    (d_x,), (d_rows,) = take_vjp(dirty), sum_vjp(x)
    for got in (taken, d_rows):
        np.testing.assert_array_equal(bits(got[:live]),
                                      bits(x[token[:live]]))
        past = -(-live // _MOVE_TILE) * _MOVE_TILE
        assert np.isfinite(np.asarray(got[:past], np.float32)).all()
        if form == "xla":
            assert not np.asarray(got[past:], np.float32).any()
        elif live < cap - _MOVE_TILE:   # or the last tile, started early
            assert np.isnan(np.asarray(got[past:], np.float32)).all()
    want = jnp.sum(clean[slot].astype(jnp.float32), axis=1).astype(rows.dtype)
    for got in (summed, d_x):
        np.testing.assert_array_equal(bits(got), bits(want))
    # and the form every other backend sums in
    np.testing.assert_array_equal(
        bits(row_sums.sum_rows_by_token(dirty, token, slot, n)), bits(want))


@pytest.mark.parametrize("where", ["none_held", "even", "heavy"])
def test_what_a_dead_row_holds_reaches_no_live_value(where, monkeypatch):
    """A share's layer with the dead rows of what `_take_rows` hands the
    grouped matmuls set to the largest finite value: output and gradients
    to x and the experts are the same bits as with what it makes itself
    (some token's row, or zeros), at both capacities."""
    cfg, params, _ = _model(SHARE)
    p = jax.tree.map(lambda a: a[0], params["layers"])
    bias = p["router_bias"].at[4:8].add(
        {"none_held": -10.0, "even": 0.0, "heavy": 0.2}[where])
    t, k = 512, cfg.experts_per_token
    h = jax.random.normal(jax.random.PRNGKey(11), (t, cfg.d_model))

    def value_and_grads():
        def program(h, experts):
            return moe.moe_layer(
                h, p["router"], experts, k, cfg.norm_topk_prob,
                score="sigmoid", router_bias=bias, held=(4, 4))[0]
        return jax.jit(jax.value_and_grad(
            lambda h, e: jnp.sum(program(h, e) ** 2), argnums=(0, 1)))(
                h, p["experts"])

    want = value_and_grads()
    take, shapes = moe._take_rows, []

    def take_and_dirty(x, token, slot, live):
        rows = take(x, token, slot, live)
        shapes.append(rows.shape[0])
        return jnp.where((jnp.arange(rows.shape[0]) >= live)[:, None],
                         jnp.finfo(rows.dtype).max, rows)

    monkeypatch.setattr(moe, "_take_rows", take_and_dirty)
    got = value_and_grads()
    assert set(shapes) == {1024, 2048}
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("where", ["all_held", "none_held", "even", "heavy"])
def test_nothing_is_dropped_through_the_token_order_combine(where, monkeypatch):
    """`test_nothing_is_dropped_at_either_extreme`'s four cases, output and
    gradients to x and the experts against the reference, with the TPU's
    form of `_sum_rows` in the layer (forward, and backward as
    `_take_rows`' transpose)."""
    calls = []

    def combine(rows, token, slot, live):
        calls.append(rows.shape)
        return _in_token_order(rows, token, slot, live)

    monkeypatch.setattr(moe, "sum_rows_by_token", combine)
    test_nothing_is_dropped_at_either_extreme(where)
    # both capacities' branches, forward and backward
    assert {shape[0] for shape in calls} == {1024, 2048} and len(calls) >= 4


def test_routing_stats_counts_the_held_pairs_of_every_layer():
    cfg, params, model = _model(SHARE)
    toks = _tokens(8)
    chosen = np.asarray(ref.routing(params, toks[:, :-1], toks[:, 1:], model))
    want = ((chosen >= 4) & (chosen < 8)).sum(axis=(1, 2))
    assert len(want) == cfg.n_layers - cfg.n_dense_layers + cfg.mtp_depth
    np.testing.assert_array_equal(
        mla_moe.routing_stats(params, toks, cfg), want)
    # and over the rows of the capacity each block runs at
    caps = np.asarray(moe.share_capacities(
        2 * 24, cfg.experts_per_token, 4, cfg.n_experts))
    np.testing.assert_allclose(
        mla_moe.routing_loads(params, toks, cfg),
        [rows / caps[np.sum(rows >= caps[:-1])] for rows in want], rtol=1e-6)


def test_seeded_weights_have_the_scales_the_cell_counts_on():
    cfg = mla_moe.MlaMoeConfig.tiny(dtype=jnp.float32)
    p = mla_moe.init(cfg, jax.random.PRNGKey(0))
    rms = lambda a: float(jnp.sqrt(jnp.mean(jnp.square(a))))
    # rows of unit RMS, whatever the width; the matmuls fan-in scaled
    assert abs(rms(p["embed"]) - 1.0) < 0.02
    assert abs(rms(p["lm_head"]) - cfg.d_model ** -0.5) < 0.01
    assert abs(rms(p["layers"]["wo"])
               - (cfg.n_heads * cfg.v_head_dim) ** -0.5) < 0.01
    assert abs(rms(p["layers"]["router"]) - 0.02) < 0.002
    assert abs(rms(p["layers"]["router_bias"]) - 0.01) < 0.004
    assert all(rms(p["layers"][k]) == 1.0 for k in ("attn_norm", "mlp_norm"))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_unit_embedding_rows_keep_the_tokens_apart_at_the_router(seed):
    """What evens the experts' loads at seeded weights (`init`'s
    docstring): the part of the first router's logits that all tokens
    share, over the part that tells them apart, grows when the embedding
    is scaled down to fan-in and the stream is left to the sublayers."""
    cfg = mla_moe.MlaMoeConfig.tiny(
        vocab_size=256, n_layers=2, dtype=jnp.float32, remat=False)
    key = jax.random.PRNGKey(seed)
    params = mla_moe.init(cfg, key)
    toks = jax.random.randint(jax.random.fold_in(key, 1), (4, 128), 0, 256)
    positions = jnp.broadcast_to(jnp.arange(128), (4, 128))

    def shared_over_own(embed):
        x = mla_moe.dense_layer(
            embed[toks], jax.tree.map(lambda a: a[0], params["dense"]),
            positions, cfg, None, None)
        p = jax.tree.map(lambda a: a[0], params["layers"])
        h = blocks.rms_norm(mixers.mla_sublayer(x, p, positions, cfg),
                              p["mlp_norm"], cfg.norm_eps)
        logits = np.asarray(h.reshape(4 * 128, -1) @ p["router"])
        return logits.mean(axis=0).std() / logits.std(axis=0).mean()

    unit = shared_over_own(params["embed"])
    scaled_down = shared_over_own(params["embed"] * cfg.d_model ** -0.5)
    # 0.11-0.15 against 0.19-0.56 over seeds 0-5; sampling alone gives 0.044
    assert unit < 0.75 * scaled_down
    assert unit < 0.17


def test_mtp_shift_and_mask():
    targets = jnp.arange(1, 11).reshape(2, 5)
    shifted, mask = blocks.mtp_targets(targets)
    np.testing.assert_array_equal(shifted[:, :-1], targets[:, 1:])
    np.testing.assert_array_equal(mask, [[1, 1, 1, 1, 0]] * 2)
    rows = jnp.array([[1.0] * 5, [0.0] * 5])
    np.testing.assert_array_equal(blocks.mtp_targets(targets, rows)[1],
                                  [[1, 1, 1, 1, 0], [0] * 5])
    # the MTP loss does not see the last position's (padded) target, and
    # its weight is `mtp_loss_coef`
    cfg, params, model = _model(SHARE)
    toks = _tokens(9)
    with jax.default_matmul_precision("highest"):
        hidden, _ = mla_moe.forward_hidden(params, toks[:, :-1], cfg)
        h_mtp, _ = mla_moe.mtp_hidden(params, hidden, toks[:, 1:], cfg)
        lg = (h_mtp @ params["lm_head"])[:, :-1]
        ce_mtp = -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(lg, -1), toks[:, 2:, None], -1))
        total = mla_moe.loss_fn(params, {"tokens": toks}, cfg)
        main = mla_moe.loss_fn(
            params, {"tokens": toks}, dataclasses.replace(cfg, mtp_depth=0))
    np.testing.assert_allclose(total - main, cfg.mtp_loss_coef * ce_mtp,
                               rtol=1e-4)
    np.testing.assert_allclose(
        ce_mtp, ref.loss_terms(params, toks[:, :-1], toks[:, 1:], model)[1],
        rtol=RTOL)


def test_rope_interleave_is_a_common_permutation():
    """The program's even-then-odd order against the reference's in-place
    pairs: q.k products are equal. And the order is made on the WEIGHTS:
    the permuted weight's outputs are the plain weight's outputs permuted,
    to the last bit (a permutation of a linear map's output channels), for
    the per-head up-projection and for the shared rotary key's columns."""
    cfg = mla_moe.MlaMoeConfig.tiny()
    pairs = partial(mixers.interleaved, config=cfg)
    q, k = (jax.random.normal(jax.random.PRNGKey(i), (1, 12, 2, 8))
            for i in (0, 1))
    pos = jnp.arange(12)[None]
    rope = partial(blocks.rope, positions=pos, theta=cfg.rope_theta)
    got = jnp.einsum("bshr,bthr->bhst", rope(pairs(q)), rope(pairs(k)))
    want = jnp.einsum("shr,thr->hst", ref._rope(q[0], cfg.rope_theta, True),
                      ref._rope(k[0], cfg.rope_theta, True))
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(pairs(jnp.arange(8)), [0, 2, 4, 6, 1, 3, 5, 7])
    plain = dataclasses.replace(cfg, rope_interleave=False)
    np.testing.assert_array_equal(
        mixers.interleaved(jnp.arange(8), plain), jnp.arange(8))
    c_q = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 48))
    w_up = jax.random.normal(jax.random.PRNGKey(3), (48, 2, 8))
    w_key = jax.random.normal(jax.random.PRNGKey(4), (48, 8))
    np.testing.assert_array_equal(
        jnp.einsum("bsr,rhk->bshk", c_q, pairs(w_up)),
        pairs(jnp.einsum("bsr,rhk->bshk", c_q, w_up)))
    np.testing.assert_array_equal(c_q @ pairs(w_key), pairs(c_q @ w_key))


@pytest.mark.parametrize("parts", [False, True], ids=["whole", "in_parts"])
@pytest.mark.parametrize("rule,tile,static,n_diagonal,shape", [
    (True, 128, True, 0, (2, 256, 2, 192, 128)),
    (True, 128, True, 0, (1, 200, 3, 24, 16)),
    # rows of ten steps: one loop a grid row over the plan's table, the
    # parts' gradients its carry
    (True, 128, False, 0, (1, 1280, 2, 24, 16)),
    # a noised and a clean copy of 256 tokens in two tiles a side: the x_t
    # diagonal tile keeps its diagonal sub-tiles alone
    (BlockDiffusion(256, 4), 256, True, 1, (2, 512, 2, 192, 128)),
    # the same over partial blocks: tiles that end past the sequence or
    # straddle the two copies; what is kept of two of those lies on their
    # diagonal sub-tiles too
    (BlockDiffusion(300, 4), 256, True, 3, (1, 600, 3, 24, 16)),
    # four tiles a side: an x_t row runs an unmasked, a masked and a
    # diagonal step, and they are not one contiguous range
    (BlockDiffusion(512, 4), 256, True, 2, (1, 1024, 2, 24, 16)),
], ids=["192_128", "24_16_partial_blocks", "24_16_loop",
        "block_diffusion_192_128", "block_diffusion_24_16_partial_blocks",
        "block_diffusion_24_16_rows"])
def test_flash_attention_key_width_differs_from_value_width(
        rule, tile, static, n_diagonal, shape, parts):
    """q, k [B, S, H, 192], v [B, S, H, 128] through the Pallas kernels in
    the interpreter against the jax.numpy oracle: forward, dq, dk, dv. In
    parts: q as (q, q_rope), k as (k, ONE rotary key for all heads): the
    values and all five gradients, the rotary key's summed over heads,
    against the oracle on the concatenated q and k. Causal, unrolled
    (`static`) and as a loop, and under a rule whose plan has DIAGONAL steps
    (`n_diagonal` in each kernel): the one set of kernels runs both forms of
    a call under every kind of plan."""
    b, s, h, d_qk, d_v = shape
    plans = block_schedule(s, s, *[_clamp_block(tile, s)] * 2, rule)
    assert [(p.static, p.steps_diagonal) for p in plans.values()] == [
        (static, n_diagonal)] * 3
    q, k = (jax.random.normal(jax.random.PRNGKey(i), (b, s, h, d_qk))
            for i in (0, 1))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, h, d_v))
    do = jax.random.normal(jax.random.PRNGKey(3), (b, s, h, d_v))
    args = (q, k, v)
    if parts:
        args = (q[..., :d_v], q[..., d_v:], k[..., :d_v], k[:, :, :1, d_v:], v)

    def whole(*a):
        if not parts:
            return a
        q, q_rope, k, k_rope, v = a
        k_rope = jnp.broadcast_to(k_rope, q_rope.shape)
        return (jnp.concatenate([q, q_rope], -1),
                jnp.concatenate([k, k_rope], -1), v)

    def oracle(*a):
        q, k, v = whole(*a)
        t = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
        return t(_reference_attention(t(q), t(k), t(v), rule, d_qk ** -0.5))

    def kernels(*a, **kw):
        if parts:
            q, q_rope, k, k_rope, v = a
            a, kw = (q, k, v), dict(kw, q_rope=q_rope, k_rope=k_rope)
        return flash_attention(*a, causal=rule, block_q=tile, block_k=tile,
                               **kw)

    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(partial(kernels, interpret=True), *args)
        want, vjp_want = jax.vjp(oracle, *args)
        assert got.shape == (b, s, h, d_v)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        grads = vjp(do)
        assert len(grads) == len(args)
        for a, w in zip(grads, vjp_want(do)):
            assert a.shape == w.shape
            np.testing.assert_allclose(a, w, rtol=2e-3, atol=2e-4)
        # off a TPU the call is the oracle on the concatenated q and k
        np.testing.assert_allclose(kernels(*args), want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("interpret", [False, True], ids=["oracle", "kernels"])
def test_flash_attention_in_parts_on_a_mesh(interpret):
    """`flash_attention_sharded` carries the parts through its shard_map
    (batch over dp x fsdp, heads over tp; the one rotary key has no head
    axis and is read whole by every tp shard): values and all five
    gradients equal the unsharded call's, the rotary key's summed over the
    tp shards' heads; and a whole model on that mesh gives the loss of the
    model on one device."""
    from ray_tpu.ops.flash_attention import flash_attention_sharded
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    b, s, h, d, r = 4, 128, 4, 16, 8
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (b, s, h, d))
               for i in (0, 1, 2))
    q_rope = jax.random.normal(jax.random.PRNGKey(3), (b, s, h, r))
    k_rope = jax.random.normal(jax.random.PRNGKey(4), (b, s, 1, r))
    do = jax.random.normal(jax.random.PRNGKey(5), (b, s, h, d))

    def one_device(q, k, v, q_rope, k_rope):
        return flash_attention(q, k, v, q_rope=q_rope, k_rope=k_rope)

    def sharded(q, k, v, q_rope, k_rope):
        return flash_attention_sharded(
            q, k, v, mesh, q_rope=q_rope, k_rope=k_rope, interpret=interpret)

    with jax.default_matmul_precision("highest"):
        want, vjp_want = jax.vjp(one_device, q, k, v, q_rope, k_rope)
        got, vjp = jax.vjp(jax.jit(sharded), q, k, v, q_rope, k_rope)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        for a, w in zip(vjp(do), vjp_want(do)):
            assert a.shape == w.shape
            np.testing.assert_allclose(a, w, rtol=2e-3, atol=2e-4)
        if not interpret:
            cfg, params, _ = _model(SHARE)
            toks = _tokens(12, rows=4)
            np.testing.assert_allclose(
                jax.jit(lambda p: mla_moe.loss_fn(
                    p, {"tokens": toks}, cfg, mesh))(params),
                mla_moe.loss_fn(params, {"tokens": toks}, cfg), rtol=RTOL)


def test_mixtral_through_the_changed_moe_layer():
    """`mixtral.py` passes no share: `held=None` is the dispatch as it was
    (tests/test_moe_reference.py holds it to its reference). Holding ALL the
    experts as a share gives the same layer and the same experts' gradients;
    `mla_moe` on an `ep` mesh raises."""
    cfg = mixtral.MixtralConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_head=16, d_ff=32, max_seq_len=64, dtype=jnp.float32, remat=False,
        n_experts=4, experts_per_token=2)
    params = mixtral.init(cfg, jax.random.PRNGKey(0))
    assert np.isfinite(mixtral.loss_fn(params, {"tokens": _tokens(10)}, cfg))
    p0 = jax.tree.map(lambda a: a[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(12), (48, 64))

    def layer(experts, held):
        return moe.moe_layer(h, p0["moe_gate"], experts, 2, True,
                             held=held)[0]

    def energy(experts, held):
        return jnp.sum(layer(experts, held) ** 2)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(layer(p0["experts"], (0, 4)),
                                   layer(p0["experts"], None),
                                   rtol=1e-5, atol=1e-6)
        # a share's combine weights are constants, so x and the router hear
        # less; the experts' gradients are the dispatch's as it was
        g_want = jax.grad(energy)(p0["experts"], None)
        g_got = jax.grad(energy)(p0["experts"], (0, 4))
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)
    with pytest.raises(ValueError, match="held experts"):
        layer(p0["experts"], (2, 2))   # weights for 4, told it holds 2

    class EpMesh:
        shape = {"ep": 2}

    with pytest.raises(NotImplementedError):
        c2, p2, _ = _model(SHARE)
        experts.expert_sublayer(
            jnp.zeros((1, 8, 64)),
            jax.tree.map(lambda a: a[0], p2["layers"]), c2, EpMesh())


def test_counters_of_a_lowering():
    cfg, params, _ = _model(SHARE)
    toks = _tokens(11)
    before = dict(device_profiler.snapshot()["counters"])
    jax.jit(lambda p: mla_moe.loss_fn(p, {"tokens": toks}, cfg)).lower(params)
    after = device_profiler.snapshot()["counters"]
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    # the scanned expert layers lower once, the dense layer and the MTP
    # block once each
    assert delta["mla.layers"] == 3
    # each of them hands its flash call the parts its projections make
    assert delta["mla.attend_parts"] == 3
    assert delta["mtp.depth"] == 1
    assert delta["moe.experts_held"] == 2 * 4
    assert delta["moe.rows_capacity"] == 2 * toks[:, :-1].size * cfg.experts_per_token
    # a share's combine, per lowered capacity: the slots a token has (what
    # a gather of `rows[slot]` moves) and the buffer's rows (what the sum in
    # token order reads)
    t, k = toks[:, :-1].size, cfg.experts_per_token
    caps = moe.share_capacities(t, k, 4, cfg.n_experts)
    assert delta["moe.combine_slots"] == 2 * len(caps) * t * k
    assert delta["moe.combine_rows"] == 2 * sum(caps)
    assert delta["moe.experts"] == 2 * cfg.n_experts
    # once a routed block on a share (the shares behind the load-balance
    # term, which this model's loss leaves out: dead code in its program)
    assert delta["moe.counts_by_comparison"] == 2


@pytest.mark.parametrize("t, tiled, whole", [(4096, 2 + 4, 0), (512, 0, 2)],
                         ids=["tiled", "whole"])
def test_counters_say_which_buffers_row_moves_lowered_as_a_loop(
        t, tiled, whole):
    """k 4, 4 of 16 experts held: capacities 2 t and 4 t rows. At 4,096
    tokens they are two and four of `ops/row_moves.py`'s row tiles, at 512
    each is less than one and its gathers are plain, by the buffer's shape."""
    k, e, n_held, d, f = 4, 16, 4, 16, 8
    caps = moe.share_capacities(t, k, n_held, e)
    assert caps == (2 * t, 4 * t)
    assert [row_moves.row_tiles(c) for c in caps] == (
        [2, 4] if tiled else [1, 1])
    shape = jax.ShapeDtypeStruct
    before = dict(device_profiler.snapshot()["counters"])
    jax.jit(lambda x, r, w: moe.moe_layer(
        x, r, w, k, held=(4, n_held))[0]).lower(
            shape((t, d), jnp.float32), shape((d, e), jnp.float32),
            {"w_gate": shape((n_held, d, f), jnp.float32),
             "w_up": shape((n_held, d, f), jnp.float32),
             "w_down": shape((n_held, f, d), jnp.float32)})
    after = device_profiler.snapshot()["counters"]
    delta = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    assert delta.get("moe.row_moves_tiled", 0) == tiled
    assert delta.get("moe.row_moves_whole", 0) == whole
