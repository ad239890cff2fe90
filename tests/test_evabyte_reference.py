"""`models/evabyte.py` (EvaByte: EVA attention over a window's own bytes and
the chunk summaries of every earlier window in ONE softmax, a float32
residual stream, a norm with a unit offset, several next-byte heads) against
the plain float32 reference `benchmarks/reference_evabyte.py`: loss and every
gradient (phi and mu among them) on seeded weights at tiny widths (4 heads of
16, window 32, chunk 4, 3 layers, 3 prediction heads, S 128 and S 96); the
departures that each have to FAIL the same comparison; and causality."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_evabyte as ref
from ray_tpu._private import device_profiler
from ray_tpu.models import evabyte as E

# float32 against float32-"highest" (tests/test_granite_hybrid_reference.py)
LOSS_RTOL = 2e-5
GRAD_ATOL = 3e-5
# what a departure has to miss the reference by, in tolerances (`_miss`)
CONTROL_MISS = 20
SEQS = (128, 96)


def _fields(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if f.name != "dtype"}


def _tokens(seq, seed=1, rows=2):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0,
                              320)


@functools.lru_cache(maxsize=None)
def _case():
    cfg = E.EvaByteConfig.tiny(dtype=jnp.float32, loss_chunk_size=40)
    params = E.init(cfg, jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(9), 64))

    def moved(path, a):
        """The norms' g away from 0 and phi and mu random and of the order
        of 1, so that each is seen; the matrices eight times `init_std`, so
        that the softmaxes are far from flat."""
        names = "".join(str(k) for k in path)
        if "norm" in names:
            return 0.3 * jax.random.normal(next(keys), a.shape)
        if "phi" in names or "mu" in names:
            return jax.random.normal(next(keys), a.shape)
        return a * 8

    return cfg, jax.tree_util.tree_map_with_path(moved, params)


@functools.lru_cache(maxsize=None)
def _got(seq):
    cfg, params = _case()
    toks = _tokens(seq)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: E.loss_fn(
            p, {"inputs": toks[:, :-1], "targets": toks[:, 1:]}, cfg))(params)


def _reference_now(seq):
    cfg, params = _case()
    toks = _tokens(seq)
    return jax.value_and_grad(lambda p: ref.loss_value(
        p, toks[:, :-1], toks[:, 1:], _fields(cfg)))(params)


_reference = functools.lru_cache(maxsize=None)(_reference_now)


def _leaves(tree):
    return {jax.tree_util.keystr(k): v
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


_LEAVES = sorted(_leaves(jax.eval_shape(
    lambda: E.init(E.EvaByteConfig.tiny(), jax.random.PRNGKey(0)))))


@pytest.mark.parametrize("seq", SEQS)
def test_the_loss_matches_the_reference(seq):
    assert float(_got(seq)[0]) == pytest.approx(float(_reference(seq)[0]),
                                                rel=LOSS_RTOL)


@pytest.mark.parametrize("seq", SEQS)
@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_gradient_matches_the_reference(leaf, seq):
    got = _leaves(_got(seq)[1])[leaf]
    want = _leaves(_reference(seq)[1])[leaf]
    assert bool(jnp.all(jnp.isfinite(got)))
    scale = float(jnp.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got / scale, want / scale, atol=GRAD_ATOL)


def _miss(want, seq=128):
    """How far the program is from the reference result `want` (loss,
    gradients), in TOLERANCES: the loss's relative error over `LOSS_RTOL` or
    a gradient leaf's (of its largest entry) over `GRAD_ATOL`, whichever is
    worse; under 1 is a match."""
    loss, grads = _got(seq)
    worst = abs(float(loss) - float(want[0])) / abs(float(want[0])) \
        / LOSS_RTOL
    for got, ref_leaf in zip(jax.tree.leaves(grads),
                             jax.tree.leaves(want[1])):
        scale = max(float(jnp.abs(ref_leaf).max()),
                    float(jnp.abs(got).max()))
        worst = max(worst, float(jnp.abs(got - ref_leaf).max()) / scale
                    / GRAD_ATOL)
    return worst


def _unrope(x, theta):
    """`ref._rope` turned back: the angles negated."""
    s, _, d = x.shape
    half = d // 2
    angle = -jnp.arange(s, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _no_mu(k, v, phi, mu, chunk, plain=ref.summaries):
    return plain(k, v, phi, jnp.zeros_like(mu), chunk)


def _plain_mean(k, v, phi, mu, chunk, plain=ref.summaries):
    return plain(k, v, jnp.zeros_like(phi), mu, chunk)


def _rope_after_pooling(k, v, phi, mu, chunk, plain=ref.summaries):
    """The summaries pool the keys as they were BEFORE the rotation."""
    return plain(_unrope(k, E.EvaByteConfig.tiny().rope_theta), v, phi, mu,
                 chunk)


def _normalised_apart(scores, kept, values):
    """A softmax over the summaries and one over the window's own bytes,
    their outputs added, in place of ONE over both (one block of rows: the
    keys beyond the rows' count are the summaries, which come first)."""
    n_sum = values.shape[0] - scores.shape[0]
    out = 0.0
    for part in (slice(0, n_sum), slice(n_sum, None)):
        some = jnp.any(kept[:, part], -1, keepdims=True)
        probs = jax.nn.softmax(
            jnp.where(kept[:, part], scores[:, part], -1e30), -1)
        out = out + jnp.where(some, probs @ values[part], 0.0)
    return out


def _own_chunks_too(n, s, window, chunk, plain=ref.visible):
    """A window sees the summaries of its OWN chunks too, those that end
    before the query."""
    earlier, own = plain(n, s, window, chunk)
    c = jnp.arange(s // chunk)[None, :]
    return earlier | ((c + 1) * chunk - 1 < n), own


def _no_unit_offset(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _one_byte_early(row_t, i):
    """Head i reads targets[t + i - 1]: every head a byte early."""
    return jnp.concatenate([row_t[:1], row_t[:-1]])[i:]


_DEPARTURES = {
    "mu_dropped": ("summaries", _no_mu),
    "pooling_a_plain_mean": ("summaries", _plain_mean),
    "rope_after_the_pooling": ("summaries", _rope_after_pooling),
    "two_kinds_normalised_apart": ("attend", _normalised_apart),
    "a_window_sees_its_own_chunks": ("visible", _own_chunks_too),
    "norm_without_its_unit_offset": ("_norm", _no_unit_offset),
    "head_i_reads_a_byte_early": ("head_targets", _one_byte_early),
}


@pytest.mark.parametrize("name", sorted(_DEPARTURES))
def test_a_departure_misses_the_reference(name, monkeypatch):
    """The reference with ONE of its pieces replaced is another function,
    and the program is not near it, though it is near the reference as
    published."""
    assert _miss(_reference(128)) < 1
    piece, other = _DEPARTURES[name]
    monkeypatch.setattr(ref, piece, other)
    assert _miss(_reference_now(128)) > CONTROL_MISS


@pytest.mark.parametrize("at", [5, 33, 70, 127])
def test_the_logits_before_a_byte_do_not_move_when_it_changes(at):
    """Causality by construction: no summary a query sees holds a byte later
    than the query. Positions from `at` on do move."""
    cfg, params = _case()
    toks = _tokens(128)[:1, :-1]
    other = toks.at[0, at].set((toks[0, at] + 7) % 320)
    with jax.default_matmul_precision("highest"):
        a = E.forward(params, toks, cfg)
        b = E.forward(params, other, cfg)
    np.testing.assert_array_equal(np.asarray(a[:, :at]), np.asarray(b[:, :at]))
    assert float(jnp.abs(a[:, at:] - b[:, at:]).max()) > 1e-4
    # and a later WINDOW's queries see it through its chunk's summary
    if at < 96:
        later = (at // 32 + 1) * 32
        assert float(jnp.abs(a[:, later:] - b[:, later:]).max()) > 1e-5


def test_the_references_logits_are_the_programs():
    cfg, params = _case()
    toks = _tokens(96)[0, :-1]
    with jax.default_matmul_precision("highest"):
        got = E.forward(params, toks[None], cfg)[0]
    want = ref.logits(params, toks, _fields(cfg))
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_row_mask_is_the_loss_of_the_rows_it_keeps():
    """The harness's check: `mask` [B, S] keeps the first rows; the loss is
    the reference's over those rows alone."""
    cfg, params = _case()
    toks = _tokens(96, rows=3)
    mask = (jnp.arange(3) < 2)[:, None] * jnp.ones((1, 96))
    with jax.default_matmul_precision("highest"):
        got = E.loss_fn(params, {"inputs": toks[:, :-1],
                                 "targets": toks[:, 1:], "mask": mask}, cfg)
    want = ref.loss(params, toks[:2, :-1], toks[:2, 1:], _fields(cfg))
    assert float(got) == pytest.approx(want, rel=LOSS_RTOL)


def test_head_targets_shift_and_mask():
    targets = jnp.arange(10, 16)[None]                    # S 6
    t, w = E.head_targets(targets, None, 3)
    assert t.shape == w.shape == (1, 6, 3)
    assert t[0, :, 1].tolist() == [11, 12, 13, 14, 15, 15]
    assert t[0, :, 2].tolist() == [12, 13, 14, 15, 15, 15]
    np.testing.assert_allclose(w[0, :, 2], [1 / 12] * 4 + [0, 0])
    np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-6)


@pytest.mark.parametrize("policy", ["full", "dots", "residuals"])
@pytest.mark.parametrize("rows", [8192, 24])
def test_remat_and_the_mlps_row_blocks_change_nothing(policy, rows,
                                                      monkeypatch):
    """The MLP whole (S 96 is under `_MLP_ROWS`) and in four blocks of 24
    rows, under each policy, against the layers with no remat."""
    cfg, params = _case()
    toks = _tokens(96)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    grad = lambda c: jax.grad(lambda p: E.loss_fn(p, batch, c))(params)  # noqa: E731
    plain = grad(dataclasses.replace(cfg, remat=False))
    monkeypatch.setattr(E, "_MLP_ROWS", rows)
    under = grad(dataclasses.replace(cfg, remat_policy=policy))
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(under)):
        scale = float(jnp.abs(a).max())
        np.testing.assert_allclose(a / scale, b / scale, atol=2e-6)


def test_lowering_counts_the_eva_call_and_the_heads_groups():
    """One layer body is traced whatever the depth; the rule's kept scores
    a (batch, head) by kind, the CE's groups."""
    cfg, params = _case()
    toks = _tokens(128)
    before = device_profiler.snapshot()["counters"]
    jax.jit(lambda p: E.loss_fn(
        p, {"inputs": toks[:, :-1], "targets": toks[:, 1:]}, cfg)).lower(
            params)
    after = device_profiler.snapshot()["counters"]
    moved = {k: after[k] - before.get(k, 0) for k in (
        "eva.calls", "eva.scores_local", "eva.scores_summary", "ce.groups")}
    # 4 windows of 32: 4 x 528 own bytes, 32 x 8 x (0 + 1 + 2 + 3) summaries
    assert moved == {"eva.calls": 1, "eva.scores_local": 2112,
                     "eva.scores_summary": 1536, "ce.groups": 3}


def test_param_axes_match_the_parameters():
    cfg = E.EvaByteConfig.tiny()
    shapes = jax.eval_shape(lambda: E.init(cfg, jax.random.PRNGKey(0)))
    flat = _leaves(shapes)
    named = {jax.tree_util.keystr(k): v for k, v in
             jax.tree_util.tree_leaves_with_path(
                 E.param_logical_axes(cfg),
                 is_leaf=lambda x: isinstance(x, tuple))}
    assert sorted(flat) == sorted(named)
    for key, shape in flat.items():
        assert len(named[key]) == len(shape.shape), key
    assert sum(int(np.prod(a.shape)) for a in flat.values()) \
        == cfg.num_params()


def test_the_published_count_of_parameters():
    """ISSUE 57's table: a layer 202,391,552 (q, k, v, o 67,108,864; gate,
    up, down 135,266,304; two norms 8,192; phi and mu 8,192); 32 layers +
    the embedding 1,310,720 + the head 10,485,760 + the final norm =
    6,488,330,240, the published 6.5 B; the four held 821,366,784."""
    c = E.EvaByteConfig()
    assert E.layer_num_params(c) == 67_108_864 + 135_266_304 + 8_192 + 8_192 \
        == 202_391_552
    assert E.layer_num_params(c) * 32 + 1_310_720 + 10_485_760 + 4_096 \
        == c.num_params() == 6_488_330_240
    assert E.EvaByteConfig(n_layers=4).num_params() == 821_366_784


def test_the_published_initialisation():
    """Matrices N(0, 0.01275^2); g 0; phi and mu within 128^-0.5."""
    c = E.EvaByteConfig.tiny(d_model=256, d_ff=512)
    p = E.init(c, jax.random.PRNGKey(3))
    std = float(jnp.std(p["layers"]["w_gate"].astype(jnp.float32)))
    assert std == pytest.approx(0.01275, rel=0.03)
    assert not p["final_norm"].any() and not p["layers"]["attn_norm"].any()
    for name in ("phi", "mu"):
        a = p["layers"][name].astype(jnp.float32)
        assert float(jnp.abs(a).max()) <= c.d_head ** -0.5 + 1e-3
        assert float(jnp.abs(a).max()) > 0.5 * c.d_head ** -0.5


def test_a_window_that_is_not_whole_chunks_is_refused():
    with pytest.raises(ValueError, match="whole"):
        E.EvaByteConfig.tiny(window=30)
