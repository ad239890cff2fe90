"""Model-family tests: Mixtral MoE (dense + expert-parallel) and ViT,
plus train-step integration on the 8-device CPU mesh."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import device_profiler
from ray_tpu.models import blocks, llama, mixtral, vit
from ray_tpu.ops import row_sums
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.parallel.sharding import LogicalAxisRules, logical_sharding
from ray_tpu.train.step import init_train_state, make_train_step


pytestmark = pytest.mark.slow  # stress/e2e tier (see pytest.ini)


def _f32(cfg_cls, **kw):
    base = cfg_cls.tiny()
    return cfg_cls(**{**base.__dict__, "dtype": jnp.float32,
                      "remat": False, **kw})


# ------------------------------------------------------------------ Mixtral


@pytest.fixture(scope="module")
def mx():
    cfg = _f32(mixtral.MixtralConfig)
    return cfg, mixtral.init(cfg, jax.random.PRNGKey(0))


def test_mixtral_forward_shapes(mx):
    cfg, params = mx
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    logits, aux = mixtral.forward(params, toks, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert aux.experts.shape == (cfg.n_layers, 2 * 16, cfg.experts_per_token)
    aux_loss = float(mixtral.aux_loss(aux, cfg))
    assert np.isfinite(aux_loss) and aux_loss > 0


def test_mixtral_loss_decreases(mx):
    cfg, params = mx
    import optax

    toks = jax.random.randint(jax.random.PRNGKey(2), (4, 17), 0,
                              cfg.vocab_size)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    loss = partial(mixtral.loss_fn, config=cfg)
    opt = optax.adam(1e-2)
    opt_state = opt.init(params)
    l0 = float(loss(params, batch))

    @jax.jit
    def step(params, opt_state):
        l, g = jax.value_and_grad(loss)(params, batch)
        u, opt_state = opt.update(g, opt_state, params)
        return optax.apply_updates(params, u), opt_state, l

    for _ in range(8):
        params, opt_state, l = step(params, opt_state)
    assert float(l) < l0


def test_mixtral_ep_sharded_matches_dense(mx):
    """Expert-parallel execution must agree with single-device routing.

    The single-device dispatch is dropless; the `ep` exchange is bounded by
    a capacity over its LOCAL tokens, so parity is asserted at ample
    capacity, where it drops nothing either."""
    cfg, params = mx
    cfg = mixtral.MixtralConfig(**{**cfg.__dict__, "capacity_factor": 8.0})
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0,
                              cfg.vocab_size)
    dense_logits, dense_aux = mixtral.forward(params, toks, cfg)

    mesh = build_mesh(MeshConfig(ep=4))
    sharded = jax.jit(
        partial(mixtral.forward, config=cfg, mesh=mesh))(params, toks)
    np.testing.assert_allclose(np.asarray(sharded[0]),
                               np.asarray(dense_logits),
                               rtol=2e-3, atol=2e-3)


def test_mixtral_train_step_on_mesh(mx):
    cfg, _ = mx
    import optax

    mesh = build_mesh(MeshConfig(dp=2, ep=4))
    rules = LogicalAxisRules()
    opt = optax.adamw(1e-3)
    state, shardings = init_train_state(
        partial(mixtral.init, cfg), opt, mixtral.param_logical_axes(cfg),
        mesh, jax.random.PRNGKey(0), rules)
    bs = logical_sharding(mesh, ("batch", "seq"), rules)
    step = make_train_step(
        partial(mixtral.loss_fn, config=cfg, mesh=mesh, rules=rules),
        opt, shardings, batch_sharding={"inputs": bs, "targets": bs})
    t = jax.random.randint(jax.random.PRNGKey(2), (4, 33), 0, cfg.vocab_size)
    batch = {"inputs": jax.device_put(t[:, :-1], bs),
             "targets": jax.device_put(t[:, 1:], bs)}
    state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_mixtral_param_count():
    cfg = _f32(mixtral.MixtralConfig)
    params = mixtral.init(cfg, jax.random.PRNGKey(0))
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert actual == cfg.num_params()


# ---------------------------------------------------------------------- ViT


def test_vit_forward_and_loss():
    cfg = vit.ViTConfig.tiny()
    params = vit.init(cfg, jax.random.PRNGKey(0))
    images = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32, 3))
    logits = vit.forward(params, images, cfg)
    assert logits.shape == (2, 10)
    labels = jnp.asarray([1, 7])
    loss = vit.loss_fn(params, {"images": images, "labels": labels}, cfg)
    assert np.isfinite(float(loss))


def test_vit_param_count():
    cfg = vit.ViTConfig.tiny()
    params = vit.init(cfg, jax.random.PRNGKey(0))
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert actual == cfg.num_params()


def test_vit_patchify_roundtrip():
    cfg = vit.ViTConfig.tiny()
    images = jnp.arange(2 * 32 * 32 * 3, dtype=jnp.float32).reshape(
        2, 32, 32, 3)
    patches = vit.patchify(images, cfg)
    assert patches.shape == (2, cfg.n_patches, cfg.patch_size ** 2 * 3)
    # First patch equals the top-left 8x8 block, row-major.
    expect = images[0, :8, :8, :].reshape(-1)
    np.testing.assert_array_equal(np.asarray(patches[0, 0]),
                                  np.asarray(expect))


def test_vit_trains_on_mesh():
    import optax

    cfg = vit.ViTConfig.tiny()
    mesh = build_mesh(MeshConfig(dp=8))
    rules = LogicalAxisRules()
    opt = optax.adamw(1e-3)
    state, shardings = init_train_state(
        partial(vit.init, cfg), opt, vit.param_logical_axes(cfg),
        mesh, jax.random.PRNGKey(0), rules)
    bs = logical_sharding(mesh, ("batch",), rules)
    ls = logical_sharding(mesh, ("batch",), rules)
    step = make_train_step(
        partial(vit.loss_fn, config=cfg), opt, shardings,
        batch_sharding={"images": bs, "labels": ls})
    images = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3))
    labels = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 10)
    batch = {"images": jax.device_put(images, bs),
             "labels": jax.device_put(labels, ls)}
    state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))


def _plain_ce(hidden, lm_head, targets, mask=None, denominator=None):
    """The full-logits CE `chunked_ce` has to equal, for autodiff."""
    logp = jax.nn.log_softmax((hidden @ lm_head).astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is None:
        mask = jnp.ones(nll.shape, jnp.float32)
    if denominator is None:
        denominator = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(nll * mask) / denominator


def _ce_operands(seq=32, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    hidden = jax.random.normal(keys[0], (2, seq, 16)).astype(dtype)
    lm_head = (0.3 * jax.random.normal(keys[1], (16, 50))).astype(dtype)
    targets = jax.random.randint(keys[2], (2, seq), 0, 50)
    kept = (jax.random.uniform(keys[3], (2, seq)) > 0.3).astype(jnp.float32)
    return hidden, lm_head, targets, kept


def _dots(jaxpr):
    """The output shapes of every `dot_general`, loop bodies' too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn.outvars[0].aval.shape
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dots(sub)


@pytest.mark.parametrize("case", [
    "no_mask", "mask_of_0_and_1", "float_weights_over_a_denominator",
    "chunk_does_not_divide_s", "cotangent_of_0.3", "bf16_operands"])
def test_llama_chunked_ce_matches_plain(case):
    """`chunked_ce`'s loss, d / d hidden and d / d lm_head (formed in its
    forward pass, scaled in its backward) against autodiff of the
    full-logits CE on the same operands."""
    hidden, lm_head, targets, kept = _ce_operands(
        seq=29 if case == "chunk_does_not_divide_s" else 32,
        dtype=jnp.bfloat16 if case == "bf16_operands" else jnp.float32)
    mask, denominator = {
        "no_mask": (None, None),
        "float_weights_over_a_denominator": (1.7 * kept, 13.0),
    }.get(case, (kept, None))
    cotangent = 0.3 if case == "cotangent_of_0.3" else 1.0
    want, (want_dh, want_dw) = jax.value_and_grad(
        lambda h, w: cotangent * _plain_ce(h, w, targets, mask, denominator),
        argnums=(0, 1))(hidden, lm_head)
    got, (got_dh, got_dw) = jax.jit(jax.value_and_grad(
        lambda h, w: cotangent * blocks.chunked_ce(
            h, w, targets, mask, chunk=8, denominator=denominator),
        argnums=(0, 1)))(hidden, lm_head)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    # bf16: the products round to 8 bits, and d lm_head adds its chunks up
    # in bf16 where the plain CE has one product
    rel = 2e-2 if case == "bf16_operands" else 1e-5
    for g, w in ((got_dh, want_dh), (got_dw, want_dw)):
        assert g.dtype == w.dtype and g.shape == w.shape
        w = np.asarray(w.astype(jnp.float32))
        assert np.abs(w).max() > 0
        np.testing.assert_allclose(np.asarray(g.astype(jnp.float32)), w,
                                   rtol=0, atol=rel * np.abs(w).max())


def test_llama_chunked_ce_undifferentiated_forms_no_gradient():
    """`jax.jit(chunked_ce)` with no gradient asked for multiplies hidden by
    lm_head and nothing else: no [rows, V] x [V, D] product (d hidden) and
    no [D, rows] x [rows, V] (d lm_head), in the scan or in the remainder's
    chunk. Differentiated, each of the two bodies holds the three."""
    hidden, lm_head, targets, kept = _ce_operands(seq=29)

    def loss(h, w):
        return blocks.chunked_ce(h, w, targets, kept, chunk=8)

    assert list(_dots(jax.make_jaxpr(loss)(hidden, lm_head).jaxpr)) == [
        (2, 8, 50), (2, 5, 50)]
    assert sorted(_dots(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        hidden, lm_head).jaxpr)) == sorted([
            (2, 8, 50), (2, 8, 16), (16, 50), (2, 5, 50), (2, 5, 16),
            (16, 50)])
    # through a model's loss_fn too: llama's, chunked against whole
    cfg = _f32(llama.LlamaConfig)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 30), 0,
                              cfg.vocab_size)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:],
             "mask": kept[:, :29]}
    ccfg = llama.LlamaConfig(**{**cfg.__dict__, "loss_chunk_size": 8})
    g1 = jax.grad(lambda p: llama.loss_fn(p, batch, cfg))(params)
    g2 = jax.grad(lambda p: llama.loss_fn(p, batch, ccfg))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


# ----------------------------------------------------------------------- T5


def test_t5_forward_and_param_count():
    from ray_tpu.models import t5

    cfg = t5.T5Config.tiny()
    params = t5.init(cfg, jax.random.PRNGKey(0))
    actual = sum(x.size for x in jax.tree.leaves(params))
    assert actual == cfg.num_params()
    src = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 1,
                             cfg.vocab_size)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 1,
                             cfg.vocab_size)
    logits = t5.forward(params, src, tgt, cfg)
    assert logits.shape == (2, 8, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_t5_decoder_is_causal_and_masks_pad():
    from ray_tpu.models import t5

    cfg = t5.T5Config.tiny()
    params = t5.init(cfg, jax.random.PRNGKey(0))
    src = jax.random.randint(jax.random.PRNGKey(1), (1, 10), 1,
                             cfg.vocab_size)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 1,
                             cfg.vocab_size)
    base = t5.forward(params, src, tgt, cfg)
    # mutating a FUTURE target token must not change earlier positions
    tgt2 = tgt.at[0, 5].set((int(tgt[0, 5]) + 1) % cfg.vocab_size or 1)
    pert = t5.forward(params, src, tgt2, cfg)
    np.testing.assert_allclose(np.asarray(base[0, :5]),
                               np.asarray(pert[0, :5]), rtol=1e-5)
    # mutating a PADDED source position must not change decoder logits
    src_pad = src.at[0, 7:].set(cfg.pad_id)
    a = t5.forward(params, src_pad, tgt, cfg)
    src_pad2 = src_pad.at[0, 8].set(cfg.pad_id)  # same mask, same tokens
    b = t5.forward(params, src_pad2, tgt, cfg)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_t5_learns_copy_task():
    import optax

    from ray_tpu.models import t5

    cfg = t5.T5Config.tiny(vocab_size=32)
    params = t5.init(cfg, jax.random.PRNGKey(0))
    opt = optax.adam(3e-3)
    opt_state = opt.init(params)
    rng = np.random.default_rng(0)

    @jax.jit
    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: t5.loss_fn(p, batch, cfg))(params)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    def batch():
        src = rng.integers(3, 32, (8, 6)).astype(np.int32)
        tgt = np.concatenate(
            [np.full((8, 1), 1, np.int32), src], axis=1)  # BOS + copy
        return {"src": jnp.asarray(src), "tgt": jnp.asarray(tgt)}

    first = None
    for i in range(400):
        params, opt_state, loss = step(params, opt_state, batch())
        if first is None:
            first = float(loss)
    assert float(loss) < 0.5 * first, (first, float(loss))


def test_t5_greedy_decode_shapes():
    from ray_tpu.models import t5

    cfg = t5.T5Config.tiny()
    params = t5.init(cfg, jax.random.PRNGKey(0))
    src = jax.random.randint(jax.random.PRNGKey(1), (3, 10), 1,
                             cfg.vocab_size)
    out = t5.greedy_decode(params, src, cfg, max_len=7)
    assert out.shape == (3, 7)
    assert np.all(np.asarray(out[:, 0]) == 1)


def test_t5_trains_on_mesh():
    import optax

    from ray_tpu.models import t5

    cfg = t5.T5Config.tiny()
    mesh = build_mesh(MeshConfig(dp=4, tp=2))
    rules = LogicalAxisRules()
    opt = optax.adamw(1e-3)
    state, shardings = init_train_state(
        partial(t5.init, cfg), opt, t5.param_logical_axes(cfg),
        mesh, jax.random.PRNGKey(0), rules)
    bs = logical_sharding(mesh, ("batch", None), rules)
    step = make_train_step(
        partial(t5.loss_fn, config=cfg), opt, shardings,
        batch_sharding={"src": bs, "tgt": bs})
    src = jax.random.randint(jax.random.PRNGKey(1), (8, 12), 1,
                             cfg.vocab_size)
    tgt = jax.random.randint(jax.random.PRNGKey(2), (8, 9), 1,
                             cfg.vocab_size)
    batch = {"src": jax.device_put(src, bs), "tgt": jax.device_put(tgt, bs)}
    state, m = step(state, batch)
    assert np.isfinite(float(m["loss"]))


# ------------------------------------------------- the embedding's gradient

# case: (V, the tokens' shape, how they are drawn, the table's dtype, D,
# the devices of the mesh, whether the table is the head too)
_BF16, _F32 = jnp.bfloat16, jnp.float32
_EMBED_CASES = {
    "every_token_distinct": (512, (512,), "distinct", _BF16, 640, 1, False),
    "one_token_t_times": (512, (300,), "one", _BF16, 640, 1, False),
    "ids_absent_from_the_batch": (1024, (64,), "some", _BF16, 640, 1, False),
    "v_no_multiple_of_256": (300, (4, 96), "some", _BF16, 640, 1, False),
    "v_below_one_tile": (100, (2, 150), "some", _BF16, 640, 1, False),
    "tokens_b_s": (512, (4, 128), "some", _BF16, 640, 1, False),
    "tokens_t": (512, (512,), "some", _BF16, 640, 1, False),
    "table_is_the_head_too": (300, (2, 64), "some", _BF16, 640, 1, True),
    "float32_table": (300, (4, 96), "some", _F32, 640, 1, False),
    "rows_a_power_of_two_wide": (300, (2, 48), "some", _BF16, 1024, 1, False),
    "mesh_of_two_devices": (512, (4, 128), "some", _BF16, 640, 2, False),
}


@pytest.mark.parametrize("case", sorted(_EMBED_CASES))
def test_llama_embed_rows_gradient_is_the_sum_by_token(case, monkeypatch):
    """`blocks.embed_rows` is `table[tokens]` bit for bit, and its d table,
    where the sorted sum forms it (a TPU's form, here with the platform's
    test taken out and the kernel in the Pallas interpreter), is the
    float32 scatter-add of the cotangent's rows rounded ONCE, bit for bit:
    a repeated id gets the sum of all its rows, an absent one a zero row.
    A float32 table, rows a power of two wide (XLA's scatter-add is fast
    there; the other cases' are 5 x 128) and a mesh of more than one device
    take the scatter-add and equal autodiff's gradient of `table[tokens]`
    exactly. The cotangents
    are eighths up to 8, so that a float32 sum of them is exact in any
    order."""
    v, shape, draw, dtype, d, devices, tied = _EMBED_CASES[case]
    monkeypatch.setattr(row_sums, "sums_in_order",
                        lambda dt: dt == jnp.bfloat16)
    monkeypatch.setattr(row_sums, "_sum_in_token_order", partial(
        row_sums._sum_in_token_order, interpret=True))
    t = int(np.prod(shape))
    rng = np.random.default_rng(sorted(_EMBED_CASES).index(case))
    tokens = jnp.asarray({
        "distinct": lambda: rng.permutation(v)[:t],
        "one": lambda: np.full(t, 7),
        "some": lambda: rng.integers(0, v, t),
    }[draw]().reshape(shape), jnp.int32)
    table = jnp.asarray(rng.standard_normal((v, d)), dtype)
    eighths = lambda *s: jnp.asarray(  # noqa: E731
        rng.integers(-64, 65, s) / 8, jnp.float32)
    w, h, u = eighths(*shape, d), eighths(8, d), eighths(8, v)
    mesh = build_mesh(MeshConfig(dp=devices), devices=jax.devices()[:devices])

    def loss(look_up, table):
        out = jnp.sum(look_up(table).astype(jnp.float32) * w)
        if tied:  # Granite's head: the same table, transposed
            out += jnp.sum((h.astype(dtype) @ table.T).astype(jnp.float32) * u)
        return out

    ours = partial(loss, lambda tb: blocks.embed_rows(tb, tokens, mesh))
    plain = partial(loss, lambda tb: tb[tokens])
    assert jnp.array_equal(blocks.embed_rows(table, tokens, mesh), table[tokens])
    before = device_profiler.snapshot()["counters"]
    got = jax.jit(jax.grad(ours))(table)
    after = device_profiler.snapshot()["counters"]
    sort = dtype == jnp.bfloat16 and d == 640 and devices == 1
    assert {k: after[k] - before.get(k, 0) for k in (
        "embed.grad_rows", "embed.grad_rows_sorted")} == {
            "embed.grad_rows": t, "embed.grad_rows_sorted": t * sort}
    autodiff = jax.jit(jax.grad(plain))(table)
    assert got.dtype == autodiff.dtype == dtype and got.shape == (v, d)
    if not sort:
        assert jnp.array_equal(got, autodiff)
        return
    want = jnp.zeros((v, d), jnp.float32).at[tokens].add(w).astype(dtype)
    absent = np.setdiff1d(np.arange(v), np.asarray(tokens))
    if draw != "distinct":
        assert absent.size and not np.asarray(want)[absent].any()
    if tied:
        want = want + jax.grad(lambda tb: loss(
            lambda tb: jnp.zeros(shape + (d,), dtype), tb))(table)
    assert jnp.array_equal(got, want)
    if draw == "one":  # the scatter-add rounds after every row it adds
        assert not jnp.array_equal(autodiff, want)


# --------------------------------------------------------------------------
# `chunked_ce(groups=)`: several heads side by side over one vocabulary
# --------------------------------------------------------------------------

def _grouped_operands(seq=40, groups=3, vocab=10, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(57), 4)
    hidden = jax.random.normal(keys[0], (2, seq, 16)).astype(dtype)
    lm_head = (0.3 * jax.random.normal(keys[1], (16, groups * vocab))
               ).astype(dtype)
    targets = jax.random.randint(keys[2], (2, seq, groups), 0, vocab)
    weights = jax.random.uniform(keys[3], (2, seq, groups))
    return hidden, lm_head, targets, weights


def _plain_grouped_ce(hidden, lm_head, targets, weights, denominator):
    """The plain grouped log-softmax: each V-wide group of the head's
    columns its own softmax against its own target and weight."""
    b, s, groups = targets.shape
    logp = jax.nn.log_softmax(
        (hidden @ lm_head).astype(jnp.float32).reshape(b, s, groups, -1), -1)
    nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
    return jnp.sum(nll * weights) / denominator


@pytest.mark.parametrize("case", [
    "chunk_divides_s", "chunk_does_not_divide_s", "one_chunk",
    "no_mask", "bf16_operands"])
def test_llama_chunked_ce_in_groups_matches_the_plain_grouped_softmax(case):
    """Value, d / d hidden and d / d lm_head, ONE matmul a chunk against the
    whole head (the dots' shapes say so)."""
    dtype = jnp.bfloat16 if case == "bf16_operands" else jnp.float32
    hidden, lm_head, targets, weights = _grouped_operands(dtype=dtype)
    chunk = {"chunk_does_not_divide_s": 16, "one_chunk": 40}.get(case, 8)
    if case == "no_mask":
        weights, denominator = None, None
        plain_w, plain_d = jnp.ones(targets.shape), float(targets.size)
    else:
        denominator = plain_d = 7.0
        plain_w = weights
    want, want_g = jax.value_and_grad(
        lambda h, w: _plain_grouped_ce(h, w, targets, plain_w, plain_d),
        argnums=(0, 1))(hidden, lm_head)
    loss = lambda h, w: blocks.chunked_ce(  # noqa: E731
        h, w, targets, weights, chunk=chunk, denominator=denominator,
        groups=3)
    got, got_g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(
        hidden, lm_head)
    assert float(jax.jit(loss)(hidden, lm_head)) == pytest.approx(
        float(want), rel=1e-5)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    rel = 2e-2 if case == "bf16_operands" else 1e-5
    for g, w in zip(got_g, want_g):
        assert g.dtype == w.dtype and g.shape == w.shape
        w = np.asarray(w.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(g.astype(jnp.float32)), w,
                                   rtol=0, atol=rel * np.abs(w).max())
    if case == "chunk_divides_s":
        assert sorted(_dots(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
            hidden, lm_head).jaxpr)) == sorted([
                (2, 8, 30), (2, 8, 16), (16, 30)])


def test_llama_chunked_ce_refuses_targets_that_are_not_its_groups():
    hidden, lm_head, targets, _ = _grouped_operands()
    with pytest.raises(ValueError, match="groups"):
        blocks.chunked_ce(hidden, lm_head, targets, chunk=8)
    with pytest.raises(ValueError, match="groups"):
        blocks.chunked_ce(hidden, lm_head, targets[..., 0], chunk=8, groups=3)
    with pytest.raises(ValueError, match="groups"):
        blocks.chunked_ce(hidden, lm_head[:, :29], targets, chunk=8, groups=3)


# sha256 of `chunked_ce`, value and gradients as traced, at each of the ten
# cells' head shapes (batch, S, d_model, vocabulary rows; train-4chip's a
# tp 2 shard's; chunks of 1,024): PR 56's text, which `groups=` (PR 57) must
# leave as it was
_CE_CALLS = {
    "train-1chip": ((4, 2048, 4096, 32768),
        "31efe6649826183d4edb1dcf3221dbe328c46476d5fd348eb050c8aaab1f70bd"),
    "train-4chip.shard": ((8, 2048, 4096, 16384),
        "c10aa7325b9c8cd1effc6b3f965c2f474686478582d664b546505f3d58698e7e"),
    "train-olmoe-1chip": ((4, 2048, 2048, 50304),
        "06c0184d6a5d17ed34f03758101365d2fa984719bd6b44fadec7c819f0c2a582"),
    "train-joyai-1chip": ((4, 2048, 2048, 16160),
        "d193a6592cf501ef82968470a2fb9d3fe0d3b41fa3571698b2b378517581fe8a"),
    "train-sdar-1chip": ((4, 2048, 2048, 18992),
        "9f5d9ea5aae14e5be02ee3a6d633744a11e65c963a9ee1bb0179933046f18bff"),
    "train-ling-1chip": ((4, 2048, 2560, 19648),
        "e4fe00c75b10a9feb299300ffc2f1d030cb7b280feed205bdd98504586d7367d"),
    "train-nemotron3-1chip": ((2, 2048, 4096, 16384),
        "7a5e4189e2936f9cb6ba65c82d00011bd67d8e8b31125395c5435494a506f41b"),
    "train-laguna-1chip": ((1, 8192, 2048, 12544),
        "40060c483f12a91fd4626eb0dac02163c662773778f65857acd0edbf69e5e229"),
    "train-smallthinker-1chip": ((1, 16384, 2560, 37984),
        "78f592d959aba1dae6ffb12d8bd802799308792ff2f824f9eb6b11f4a14da730"),
    "train-granite4-1chip": ((1, 32768, 2048, 100352),
        "2e9dd25c99e26e5a349d7e68837b72c45c13ef639942f817949f58aa931a43f6"),
}
_CE_WITH_WEIGHTS = \
    "7f63f6cf56bbe856bb690678b99c1c42b429d9bb7a11dd417562a5f1c8ef448b"
_CE_UNDIFFERENTIATED = \
    "c1903270e7a1ba56831e320b0bf36a93547d83f8d57c0069be886359726d87a5"


def _traced_digest(fn, shapes):
    import hashlib
    import re

    traced = jax.make_jaxpr(fn)(*shapes)
    return hashlib.sha256(re.sub(r" at 0x[0-9a-f]+", "", str(traced))
                          .encode()).hexdigest()


@pytest.mark.parametrize("cell", list(_CE_CALLS))
def test_the_ten_cells_chunked_ce_traces_to_what_it_was(cell):
    (b, s, d, v), digest = _CE_CALLS[cell]
    shaped = jax.ShapeDtypeStruct
    assert _traced_digest(
        jax.value_and_grad(
            lambda h, w, t: blocks.chunked_ce(h, w, t, chunk=1024),
            argnums=(0, 1)),
        (shaped((b, s, d), jnp.bfloat16), shaped((d, v), jnp.bfloat16),
         shaped((b, s), jnp.int32))) == digest


def test_chunked_ce_with_weights_and_undifferentiated_trace_as_they_did():
    shaped = jax.ShapeDtypeStruct
    shapes = (shaped((4, 2048, 2048), jnp.bfloat16),
              shaped((2048, 18992), jnp.bfloat16),
              shaped((4, 2048), jnp.int32), shaped((4, 2048), jnp.float32))
    assert _traced_digest(jax.value_and_grad(
        lambda h, w, t, m: blocks.chunked_ce(
            h, w, t, m, chunk=1024, denominator=8192.0),
        argnums=(0, 1)), shapes) == _CE_WITH_WEIGHTS
    assert _traced_digest(
        lambda h, w, t, m: blocks.chunked_ce(h, w, t, m, chunk=1024),
        shapes) == _CE_UNDIFFERENTIATED
