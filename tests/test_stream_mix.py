"""`ops/stream_mix.py`'s four Pallas calls, run in the Pallas interpreter on
the CPU (`stream_mix.INTERPRET`), through `models/streams.py` as a model
calls them: against `streams`' own `jnp` path and against the plain
reference `benchmarks/reference_xing.py` (float32 at `highest`) at small
sizes; with the clamp reached; with each of `tools/hc_chip_check.py`'s three
controls in the program's place, which has to MISS the limits that
`train-xing4-1chip`'s `correct` holds; and on the shapes the rule refuses,
which take the `jnp` path and count no fused row."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import reference_xing as ref
from benchmarks import train_hc_cell
from ray_tpu._private import device_profiler
from ray_tpu.models import mla_moe, streams
from ray_tpu.ops import stream_mix
from tools import hc_chip_check

SEQ = 128


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(stream_mix, "INTERPRET", True)


def _connection(seed, n=4, b=1, s=SEQ, d=128, dtype=jnp.bfloat16):
    cfg = mla_moe.MlaMoeConfig.tiny(hc_mult=n, dtype=dtype, d_model=d)
    k_p, k_a, k_x, k_c, k_g = jax.random.split(jax.random.PRNGKey(seed), 5)
    p = streams.init_connection(cfg, k_p)
    p["alpha"] = 1.0 + 0.25 * jax.random.uniform(k_a, (3,), minval=-1.0)
    X = jax.random.normal(k_x, (n, b, s, d), jnp.float32).astype(dtype)
    cot = jax.random.normal(k_c, X.shape, jnp.float32).astype(dtype)
    g = (1.0 + 0.1 * jax.random.normal(k_g, (d,))).astype(dtype)
    return cfg, p, X, cot, g


def _program(cfg, mesh=None):
    """-> (the maps, X', the gradients by X, the connection and the branch's
    weight) of one connection around y = tanh(h) * g, a fresh trace a
    call."""
    def run(X, p, g, cot):
        out, vjp = jax.vjp(
            lambda X, p, g: streams.connect(
                X, p, lambda h: (jnp.tanh(h) * g, None), cfg, mesh)[0],
            X, p, g)
        return streams.maps(X, p, cfg, mesh), out, vjp(cot)

    return jax.jit(run)


def _reference(cfg, X, p, g, cot):
    """The same from `reference_xing`, a batch row at a time, float32."""
    model = dataclasses.asdict(cfg)
    f32 = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float32), tree)
    X, p, g, cot = f32((X, p, g, cot))

    def rows(X, p, g):
        out = [ref.connection(jnp.moveaxis(X[:, r], 0, 1), p,
                              lambda h: (jnp.tanh(h) * g, None), model)[0]
               for r in range(X.shape[1])]
        return jnp.moveaxis(jnp.stack(out), 2, 0)

    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(rows, X, p, g)
        mapped = [ref.hc_maps(jnp.moveaxis(X[:, r], 0, 1), p, model)
                  for r in range(X.shape[1])]
        return mapped, out, vjp(cot)


def _frob(got, want):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _counted(names=("hc.rows_fused", "hc.rows_mixed", "hc.connections")):
    counters = device_profiler.snapshot()["counters"]
    return {name: counters.get(name, 0) for name in names}


@pytest.mark.parametrize("d", [128, 384])
@pytest.mark.parametrize("b", [1, 3])
@pytest.mark.parametrize("n", [2, 4])
def test_the_calls_are_the_jnp_path_and_the_reference(n, b, d, interpreted):
    """The maps within 1e-5 of the `jnp` path's and of the reference's (float32
    maps of the same bf16 X and phi: 1e-6 read); X', dX and d phi, which
    leave in bf16, within their rounding of the reference (2e-3 to 4e-3
    read, held to 6e-3); d alpha and d b (1e-3 to 1.3e-2 read on either
    path: sums of bf16 cotangents over as few as 128 tokens) and the
    branch's weight's gradient (a bf16 sum outside the calls) to the cell's
    2e-2, and none much further from the reference than the `jnp` path is;
    every row counted as fused."""
    cfg, p, X, cot, g = _connection(n + 10 * b + d, n, b, d=d)
    before = _counted()
    maps, out, (dX, dp, dg) = _program(cfg)(X, p, g, cot)
    grew = {k: v - before[k] for k, v in _counted().items()}
    assert grew == {"hc.rows_fused": n * b * SEQ, "hc.rows_mixed": n * b * SEQ,
                    "hc.connections": 1}
    with pytest.MonkeyPatch.context() as m:
        m.setattr(stream_mix, "INTERPRET", False)
        j_maps, j_out, (j_dX, j_dp, j_dg) = _program(cfg)(X, p, g, cot)
    for got, want in zip(maps, j_maps):
        assert got.shape == want.shape and got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, atol=1e-5)
    r_maps, r_out, (r_dX, r_dp, r_dg) = _reference(cfg, X, p, g, cot)
    for r in range(b):
        pre, post, res = r_maps[r]
        np.testing.assert_allclose(maps[0][:, r], pre.T, atol=1e-5)
        np.testing.assert_allclose(maps[1][:, r], post.T, atol=1e-5)
        np.testing.assert_allclose(maps[2][:, :, r],
                                   jnp.moveaxis(res, 0, 2), atol=1e-5)
    assert out.dtype == dX.dtype == jnp.bfloat16
    errors = {"out": _frob(out, r_out), "dX": _frob(dX, r_dX),
              **{k: _frob(dp[k], r_dp[k]) for k in ("phi", "alpha", "b")}}
    assert max(errors[k] for k in ("out", "dX", "phi")) < 6e-3, errors
    # sums over as few as 128 tokens of bf16 cotangents: the cell's limit
    assert max(errors["alpha"], errors["b"]) < train_hc_cell.VALUE_LIMIT, \
        errors
    # the branch's own bf16 sum over the tokens, fed by the calls' dy
    assert _frob(dg, r_dg) < 2e-2 and _frob(dg, j_dg) < 2e-2
    # and no further from the reference than the `jnp` path is, by much
    theirs = {"out": _frob(j_out, r_out), "dX": _frob(j_dX, r_dX),
              **{k: _frob(j_dp[k], r_dp[k]) for k in ("phi", "alpha", "b")}}
    for k, v in theirs.items():
        assert errors[k] < 1.5 * v + 2e-3, (k, errors, theirs)


def test_the_clamp_is_reached_and_stops_the_gradient_there(interpreted):
    """Biases of +-40 put H_res's pre-activations past the clamp at +-30:
    the calls' maps are finite and the `jnp` path's, and the gradient by
    those biases is zero on both paths (the clamp's flat part)."""
    cfg, p, X, cot, g = _connection(3)
    p["b"] = p["b"].at[8].set(40.0).at[13].set(-40.0)
    maps, out, (dX, dp, _) = _program(cfg)(X, p, g, cot)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(stream_mix, "INTERPRET", False)
        j_maps, j_out, (j_dX, j_dp, _) = _program(cfg)(X, p, g, cot)
    assert all(bool(jnp.isfinite(m).all()) for m in maps)
    np.testing.assert_allclose(maps[2], j_maps[2], atol=1e-5)
    assert float(dp["b"][8]) == float(j_dp["b"][8]) == 0.0
    assert float(dp["b"][13]) == float(j_dp["b"][13]) == 0.0
    assert _frob(dp["b"], j_dp["b"]) < 6e-3
    assert _frob(dX, j_dX) < 6e-3 and _frob(out, j_out) < 6e-3


@pytest.mark.parametrize(
    "control", [None, "one_sinkhorn_iteration", "static_maps", "bf16_maps"])
def test_the_controls_miss_the_cells_limits_through_the_calls(
        control, interpreted):
    """`train_hc_cell.connection_errors` under `within_limits`, what the
    cell's `correct` holds, with the Pallas calls in the program's place:
    within every limit as they are; one Sinkhorn iteration for twenty and
    alpha = 0 run THROUGH the calls and miss; bf16 maps, put where
    `connect` and `maps` get theirs, miss by the maps' limit."""
    cfg, p, X, cot, _ = _connection(7, b=2, d=256)
    before = _counted()
    with hc_chip_check.controlled(control):
        errors = train_hc_cell.connection_errors(
            cfg, dataclasses.asdict(cfg), ref, p, X, cot)
    fused = _counted()["hc.rows_fused"] - before["hc.rows_fused"]
    assert fused == (0 if control == "bf16_maps" else X.shape[0] * 2 * SEQ)
    assert train_hc_cell.within_limits(errors) == (control is None), errors
    if control == "bf16_maps":
        assert errors["hc_maps_err"] > 10 * train_hc_cell.MAPS_LIMIT
    if control is None:
        assert errors["hc_maps_err"] < train_hc_cell.MAPS_LIMIT / 10


@pytest.mark.parametrize("case", ["narrow", "ragged", "two_devices",
                                  "float32", "seven_streams", "wide"])
def test_a_shape_the_rule_refuses_takes_the_jnp_path(case, interpreted):
    """D 96 (no multiple of 128), 160 tokens (no multiple of the token tile),
    a mesh of two devices, float32 streams, seven streams (a stream's
    columns are a group's 8 rows, two of them taken), or D 4,608 at n 4 (the
    largest call's blocks pass the 16 MiB a call gets without stating a
    limit; D 4,096 fits): no row is counted as fused, and the results are
    the `jnp` path's to the bit."""
    kw = {"narrow": dict(d=96), "ragged": dict(s=160), "two_devices": {},
          "float32": dict(dtype=jnp.float32), "seven_streams": dict(n=7),
          "wide": dict(d=4608)}[case]
    cfg, p, X, cot, g = _connection(11, **kw)
    mesh = None
    if case == "two_devices":
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("dp",))
        assert stream_mix.fused(X) and not stream_mix.fused(X, mesh)
    assert not stream_mix.fused(X, mesh)
    if case == "wide":
        assert stream_mix.fused(X[..., :4096])
    before = _counted()
    got = _program(cfg, mesh)(X, p, g, cot)
    grew = {k: v - before[k] for k, v in _counted().items()}
    assert grew["hc.rows_fused"] == 0 and grew["hc.rows_mixed"] == X[..., 0].size
    with pytest.MonkeyPatch.context() as m:
        m.setattr(stream_mix, "INTERPRET", False)
        want = _program(cfg, mesh)(X, p, g, cot)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


def test_the_rows_layout_round_trips():
    """`to_rows` / `from_rows`: a group of 8 rows for H_pre, H_post and each
    row of H_res, the first n live; phi and (alpha, b) laid alike."""
    n, d = 4, 128
    v = jnp.arange(24 * 3, dtype=jnp.float32).reshape(24, 3)
    rows = stream_mix.to_rows(v, n)
    assert rows.shape == (stream_mix.rows_of(n), 3) == (48, 3)
    np.testing.assert_array_equal(stream_mix.from_rows(rows, n), v)
    np.testing.assert_array_equal(rows[4:8], 0)
    np.testing.assert_array_equal(rows[16 + 8 * 2 + 1], v[8 + 4 * 2 + 1])
    phi = jax.random.normal(jax.random.PRNGKey(0), (n, d, 24))
    laid = stream_mix.phi_rows(phi)
    assert laid.shape == (48, n * d)
    np.testing.assert_array_equal(laid[8 + 1, 2 * d + 5], phi[2, 5, n + 1])
    coef = stream_mix.coef_rows(jnp.array([2.0, 3.0, 5.0]),
                                jnp.arange(24.0), n)
    np.testing.assert_array_equal(coef[:4, 0], 2.0)
    np.testing.assert_array_equal(coef[8:12, 0], 3.0)
    np.testing.assert_array_equal(coef[24:28, 0], 5.0)
    np.testing.assert_array_equal(coef[24:28, 1], jnp.arange(12.0, 16.0))
    np.testing.assert_array_equal(coef[28:32], 0)
