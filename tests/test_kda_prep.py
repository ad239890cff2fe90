"""`ops/kda_prep.py`'s two Pallas calls, run in the Pallas interpreter on the
CPU (`kda_prep.INTERPRET`), against `mixers.kda_operands`, the plain `jnp`
lines they stand in for: at Ling's heads `[.., 32, 128]` and Solar's `[..,
64, 128]` cut to three token tiles, at the first tile (the zero halo), across
a tile boundary (the halo's three rows), at K 4, in bf16 and in float32; and
through `mixers.kda_sublayer` on the shapes the rule refuses, which take the
`jnp` lines and count no fused row."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import device_profiler
from ray_tpu.models import hybrid_moe, mixers
from ray_tpu.ops import kda_prep

TILE = 32
K = 4


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(kda_prep, "INTERPRET", True)
    monkeypatch.setattr(kda_prep, "TOKEN_TILE", TILE)


def _operands(seed, b, s, h, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 9)
    xs = [jax.random.normal(ks[i], (b, s, h, d)).astype(dtype)
          for i in range(3)]
    taps = [(0.5 * jax.random.normal(ks[3 + i], (K, h, d))).astype(dtype)
            for i in range(3)]
    cots = [jax.random.normal(ks[6 + i], (b, h, s, d)).astype(dtype)
            for i in range(3)]
    return xs, taps, cots


def _frob(got, want):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _prep_of_heads(xs, taps, dtype):
    """`kda_prep.prep` on [B, S, H, D]: it reads the matmul's rows."""
    return kda_prep.prep([x.reshape(x.shape[:2] + (-1,)) for x in xs], taps,
                         dtype)


def _with_grads(fn, dtype):
    def run(xs, taps, cots):
        out, vjp = jax.vjp(lambda xs, taps: fn(xs, taps, dtype), xs, taps)
        return out, vjp(tuple(cots))

    return jax.jit(run)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "float32"])
@pytest.mark.parametrize("heads, batch", [(32, 2), (64, 1)],
                         ids=["ling", "solar"])
def test_the_calls_are_the_jnp_lines(heads, batch, dtype, interpreted):
    """q, k, v: the `jnp` lines' TO THE BIT after the rounding to bf16 (in
    the interpreter both are XLA's CPU arithmetic; on the chip Mosaic's exp
    and rsqrt may differ in an ulp of float32, which `tools/kda_chip_check.py
    --prep` reads), within 2e-6 of them in float32, where the head's sum of
    squares is added up in another order. dx and d taps against the lines'
    own `jax.vjp` IN FLOAT32 on the same values: 2e-3 in bf16, where a call
    rounds dx once (the lines round each tap's term and add the four in
    bf16: their own error is the larger), 1e-5 in float32."""
    s, d = 3 * TILE, 128
    xs, taps, cots = _operands(heads, batch, s, heads, d, dtype)
    assert kda_prep.fused(xs[0].shape, taps[0])
    got, (got_dx, got_dtaps) = _with_grads(_prep_of_heads, dtype)(
        xs, taps, cots)
    want, (lines_dx, _) = _with_grads(mixers.kda_operands, dtype)(
        xs, taps, cots)
    f32 = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float32), tree)
    _, (want_dx, want_dtaps) = _with_grads(
        mixers.kda_operands, jnp.float32)(*f32((xs, taps, cots)))
    bf16 = dtype == jnp.bfloat16
    for a, b in zip(got, want):
        assert a.shape == (batch, heads, s, d) and a.dtype == dtype
        for rows in (slice(0, K - 1), slice(TILE, TILE + K - 1),
                     slice(2 * TILE - 1, 2 * TILE + K - 1), slice(None)):
            if bf16:
                np.testing.assert_array_equal(a[:, :, rows], b[:, :, rows])
            else:
                assert _frob(a[:, :, rows], b[:, :, rows]) < 2e-6
    limit = 2e-3 if bf16 else 1e-5
    for a, b, lines in zip(got_dx, want_dx, lines_dx):
        assert a.shape == (batch, s, heads, d) and a.dtype == dtype
        assert _frob(a, b) < limit
        # a tile's last K - 1 rows read du of the next tile's first rows
        assert _frob(a[:, TILE - K:TILE + K], b[:, TILE - K:TILE + K]) < limit
        assert _frob(a[:, -K:], b[:, -K:]) < limit
        assert _frob(a, b) <= _frob(lines, b) * (1 + 1e-3) + 1e-7
    for a, b in zip(got_dtaps, want_dtaps):
        assert a.shape == (K, heads, d) and a.dtype == dtype
        assert _frob(a, b) < limit


@pytest.mark.parametrize("bound", [None, -5.0], ids=["softplus", "bounded"])
@pytest.mark.parametrize("heads, batch", [(32, 2), (64, 1)],
                         ids=["ling", "solar"])
def test_the_decay_gates_calls_are_the_jnp_lines(heads, batch, bound,
                                                 interpreted):
    """g float32 [B, H, S, D] under both of the sublayer's forms against
    `mixers.kda_decay`: 2e-6 of it (float32 throughout; the interpreter's
    exp is XLA's); the projection's cotangent within bf16's rounding of the
    lines' own float32 vjp, `dt_bias`' and `A_log`'s gradients (sums over
    the tokens, in a block resident over the token axis, the batches and a
    head's channels added outside) within 1e-5."""
    s, d = 3 * TILE, 128
    ks = jax.random.split(jax.random.PRNGKey(heads), 4)
    a = (2.0 * jax.random.normal(ks[0], (batch, s, heads, d))).astype(
        jnp.bfloat16)
    bias = -jax.random.uniform(ks[1], (heads, d), minval=1.0, maxval=5.0)
    a_log = jnp.log(jax.random.uniform(ks[2], (heads,), minval=1.0,
                                       maxval=16.0))
    cot = jax.random.normal(ks[3], (batch, heads, s, d))

    def run(fn, a):
        out, vjp = jax.vjp(lambda a, bias, a_log: fn(a, bias, a_log, bound),
                           a, bias, a_log)
        return out, vjp(cot)

    rows = lambda a, *rest: kda_prep.gate(  # noqa: E731
        a.reshape(a.shape[:2] + (-1,)), *rest)
    got, got_grads = jax.jit(lambda: run(rows, a))()
    want, _ = jax.jit(lambda: run(mixers.kda_decay, a))()
    _, want_grads = jax.jit(
        lambda: run(mixers.kda_decay, a.astype(jnp.float32)))()
    assert got.shape == (batch, heads, s, d) and got.dtype == jnp.float32
    assert _frob(got, want) < 2e-6
    for a_, b_, limit in zip(got_grads, want_grads, (2e-3, 1e-5, 1e-5)):
        assert a_.shape == b_.shape
        assert _frob(a_, b_) < limit
    assert got_grads[0].dtype == jnp.bfloat16


def _counted():
    counters = device_profiler.snapshot()["counters"]
    return {k: counters.get(k, 0)
            for k in ("kda.prep_rows", "kda.prep_rows_fused")}


def _sublayer(seed, d=128, s=2 * TILE, mesh=None):
    cfg = hybrid_moe.HybridMoeConfig.tiny(
        kda_head_dim=d, n_heads=2, dtype=jnp.bfloat16)
    k_p, k_x = jax.random.split(jax.random.PRNGKey(seed))
    p = mixers.init_kda(cfg, k_p)
    x = jax.random.normal(k_x, (1, s, cfg.d_model)).astype(cfg.dtype)
    # a fresh trace a call: the counters count a lowering
    return cfg, x, jax.jit(lambda x, p: jax.value_and_grad(
        lambda x, p: jnp.sum(mixers.kda_sublayer(
            x, p, cfg, mesh).astype(jnp.float32) ** 2), argnums=(0, 1))(x, p))(
                x, p)


@pytest.mark.parametrize("case", ["fused", "narrow", "ragged", "two_devices"])
def test_a_shape_the_rule_refuses_takes_the_jnp_lines(case, interpreted):
    """Through `mixers.kda_sublayer`: D 128 at two tiles of tokens takes the
    calls and counts every row as fused; D 64 (no lane tile), 40 tokens (no
    multiple of the token tile) or a mesh of two devices take the `jnp`
    lines, count `kda.prep_rows_fused` 0, and give the lines' values to the
    bit (the fused layer's: within bf16's rounding of them)."""
    kw = {"fused": {}, "narrow": dict(d=64), "ragged": dict(s=40),
          "two_devices": dict(mesh=jax.sharding.Mesh(
              np.array(jax.devices()[:2]), ("dp",)))}[case]
    before = _counted()
    cfg, x, got = _sublayer(13, **kw)
    grew = {k: v - before[k] for k, v in _counted().items()}
    rows = 3 * x.shape[1] * cfg.n_heads
    assert grew == {"kda.prep_rows": rows,
                    "kda.prep_rows_fused": rows if case == "fused" else 0}
    with pytest.MonkeyPatch.context() as m:
        m.setattr(kda_prep, "INTERPRET", False)
        _, _, want = _sublayer(13, **kw)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        if case == "fused" and np.any(np.asarray(b, np.float32)):
            assert _frob(a, b) < 2e-2
        else:
            np.testing.assert_array_equal(a, b)


def test_no_call_states_a_vmem_limit_and_the_tile_is_the_cells():
    """The rule the module's docstring gives (PERF.md section 6, PR 62): the
    calls' compiler parameters name the grid's semantics and nothing else;
    both cells' sequences are whole tiles."""
    import inspect

    source = inspect.getsource(kda_prep)
    assert "vmem_limit_bytes" not in source
    assert 2048 % kda_prep.TOKEN_TILE == 0 == 8192 % kda_prep.TOKEN_TILE
    assert kda_prep.TOKEN_TILE % kda_prep.HALO == 0
