"""Pallas-op tests (interpret mode on CPU; the oracle is plain JAX)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops.flash_attention import (
    _clamp_block, block_schedule, flash_attention)


def _make_qkv(B=1, S=128, H=2, D=64, kv_heads=None, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, S, H, D), dtype=jnp.float32)
    kvh = kv_heads or H
    k = jax.random.normal(keys[1], (B, S, kvh, D), dtype=jnp.float32)
    v = jax.random.normal(keys[2], (B, S, kvh, D), dtype=jnp.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_forward(causal):
    q, k, v = _make_qkv()
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=64, block_k=64)
    ref = flash_attention(q, k, v, causal=causal, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_grads():
    q, k, v = _make_qkv(S=128)

    def loss_pallas(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, interpret=True,
                            block_q=64, block_k=64) ** 2
        )

    def loss_ref(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, use_pallas=False) ** 2)

    g1 = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_flash_attention_gqa():
    q, k, v = _make_qkv(H=4, kv_heads=2)
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=64, block_k=64)
    ref = flash_attention(q, k, v, causal=True, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_rejects_bad_heads():
    q, k, v = _make_qkv(H=4, kv_heads=3)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, use_pallas=False)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_partial_blocks(causal):
    """seq not a multiple of the block size: padding keys must be masked."""
    q, k, v = _make_qkv(S=192)
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          block_q=128, block_k=128)
    ref = flash_attention(q, k, v, causal=causal, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def loss_p(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, interpret=True,
                                       block_q=128, block_k=128) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       use_pallas=False) ** 2)

    g1 = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_attention_cross_length_causal():
    """Decode-style: 1 query over S keys must see all past keys."""
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(keys[0], (1, 64, 2, 64))
    k = jax.random.normal(keys[1], (1, 128, 2, 64))
    v = jax.random.normal(keys[2], (1, 128, 2, 64))
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=64, block_k=64)
    ref = flash_attention(q, k, v, causal=True, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def _default_blocks(s_q, s_k):
    import inspect

    params = inspect.signature(flash_attention).parameters
    return (_clamp_block(params["block_q"].default, s_q),
            _clamp_block(params["block_k"].default, s_k))


def _mask(s_q, s_k, causal, rows, cols):
    r = np.arange(rows)[:, None]
    c = np.arange(cols)[None, :]
    m = (r < s_q) & (c < s_k)
    return m & (c <= r + (s_k - s_q)) if causal else m


# (s_q, s_k, block_q, block_k, causal): executed/needed bound of fwd/dq, of dk/dv
_SCHEDULES = {
    "s2048-default": ((2048, 2048, None, None, True), 1.25, 1.25),
    # causal steps are cut to the owned block: 512 queries, 1,024 keys
    "s2048-512x1024": ((2048, 2048, 512, 1024, True), 1.25, 1.5),
    "s2048-1024x512": ((2048, 2048, 1024, 512, True), 1.5, 1.25),
    "s2048-256x512": ((2048, 2048, 256, 512, True), 1.125, 1.25),
    "s2048-256x256": ((2048, 2048, 256, 256, True), 1.125, 1.125),
    "s2048-noncausal": ((2048, 2048, 512, 1024, False), 1.0, 1.0),
    "s4096-default": ((4096, 4096, None, None, True), 1.125, 1.125),
    "s8192-default": ((8192, 8192, None, None, True), 1.0625, 1.0625),
    "cross-128-over-384": ((128, 384, 128, 128, True), 1.2, 1.2),
    "cross-64-over-128": ((64, 128, None, None, True), 1.33, 1.33),
    "more-queries-than-keys": ((384, 128, 128, 128, True), 2.0, 2.0),
    "s320-padded": ((320, 320, 128, 128, True), 1.92, 1.92),
    "s320-padded-noncausal": ((320, 320, 128, 256, False), 1.92, 1.92),
    "s192-on-128": ((192, 192, 128, 128, True), 2.66, 2.66),
    "s100-one-block": ((100, 100, None, None, True), 2.0, 2.0),
    "s128-one-block": ((128, 128, None, None, False), 1.0, 1.0),
}


@pytest.mark.parametrize("case", sorted(_SCHEDULES))
def test_block_schedule_against_the_mask(case):
    """Every step block_schedule calls unmasked has no masked score in it,
    the steps cover each needed score exactly once, and executed over
    needed is what a brute-force count of the mask gives."""
    (s_q, s_k, block_q, block_k, causal), *bounds = _SCHEDULES[case]
    if block_q is None:
        block_q, block_k = _default_blocks(s_q, s_k)
    plans = block_schedule(s_q, s_k, block_q, block_k, causal)
    assert plans["dq"] == plans["fwd"]
    for name, bound in zip(("fwd", "dkv"), bounds):
        plan = plans[name]
        rows = max(t[0] + t[1] for t in plan.tiles)
        cols = max(t[2] + t[3] for t in plan.tiles)
        mask = _mask(s_q, s_k, causal, rows, cols)
        painted = np.zeros((rows, cols), dtype=np.int32)
        for q0, nq, k0, nk, masked in plan.tiles:
            painted[q0:q0 + nq, k0:k0 + nk] += 1
            # the owned block, and a step of the plan's width along the other
            assert (nq, nk) == ((block_q, plan.width) if name == "fwd"
                                else (plan.width, block_k))
            if causal:
                # at most square: the diagonal never cuts a step twice
                # the size of what it leaves visible
                assert plan.width <= max(block_q, block_k)
            if masked:
                continue
            # an unmasked step: every score of the owned block's real rows
            # (forward: queries; dk/dv: keys) is valid, and the walked axis
            # has no padding in it at all
            if name == "fwd":
                assert k0 + nk <= s_k
                assert mask[q0:min(q0 + nq, s_q), k0:k0 + nk].all(), (q0, k0)
            else:
                assert q0 + nq <= s_q
                assert mask[q0:q0 + nq, k0:min(k0 + nk, s_k)].all(), (q0, k0)
        assert painted.max() == 1
        assert (painted[mask] == 1).all()
        assert plan.static == (max(len(r) for r in plan.rows) <= 4)
        assert [t[4] for t in plan.tiles] == [
            masked for row in plan.rows for _, masked in row]
        if plan.static:
            # unrolled: a step is masked only if a score in it is not valid
            for q0, nq, k0, nk, masked in plan.tiles:
                real = (mask[q0:q0 + nq, k0:k0 + nk] if name == "fwd" else
                        mask[q0:q0 + nq, k0:min(k0 + nk, s_k)])
                assert masked == (not real[:s_q - q0].all() if name == "fwd"
                                  else not real.all()), (q0, k0)
        assert plan.steps_unmasked == sum(not t[4] for t in plan.tiles)
        assert plan.steps_masked == sum(t[4] for t in plan.tiles)
        np.testing.assert_allclose(plan.executed_over_needed,
                                   painted.sum() / mask.sum())
        assert plan.executed_over_needed <= bound + 1e-9, name


def test_block_schedule_starting_point():
    """The schedule before PR 26 (steps of block_q x block_k up to the
    diagonal, 512 x 1024) executed 1.5x the causal half at S 2048; the
    defaults now execute at most 1.25x in all three kernels."""
    s, block_q, block_k = 2048, 512, 1024
    old = sum(-(-(qi + 1) * block_q // block_k) * block_q * block_k
              for qi in range(s // block_q))
    assert old / _mask(s, s, True, s, s).sum() == pytest.approx(1.5, abs=1e-3)
    for plan in block_schedule(s, s, *_default_blocks(s, s), True).values():
        assert plan.executed_over_needed <= 1.25
        # six of the ten steps a head lie wholly below the diagonal
        assert (plan.steps_unmasked, plan.steps_masked) == (6, 4)


# Shapes where a grid row runs several steps, some unmasked and some masked:
# plans short enough to unroll (`static`, at most 4 steps a row: each step
# masked or not by itself) and longer ones (loops: unmasked only where a
# whole loop is, so never in a causal call).
_MIXED = {
    "s512-128x128": dict(s_q=512, s_k=512, block_q=128, block_k=128),
    "s512-128x256": dict(s_q=512, s_k=512, block_q=128, block_k=256),
    "s512-256x128": dict(s_q=512, s_k=512, block_q=256, block_k=128),
    "cross-128-over-384": dict(s_q=128, s_k=384, block_q=128, block_k=128),
    "s320-128x128": dict(s_q=320, s_k=320, block_q=128, block_k=128),
    "s320-128x256-noncausal": dict(s_q=320, s_k=320, block_q=128,
                                   block_k=256, causal=False),
    "s448-128x256-noncausal": dict(s_q=448, s_k=448, block_q=128,
                                   block_k=256, causal=False),
    "s768-128x128-loops": dict(s_q=768, s_k=768, block_q=128, block_k=128,
                               static=False),
    "s704-128x128-noncausal-loops": dict(s_q=704, s_k=704, block_q=128,
                                         block_k=128, causal=False,
                                         static=False),
}


@pytest.mark.parametrize("case", sorted(_MIXED))
def test_flash_attention_interior_and_edge_steps(case):
    """Forward and gradients against the oracle where a grid row runs
    unmasked steps and masked ones, unrolled or in loops."""
    spec = dict(_MIXED[case])
    s_q, s_k = spec.pop("s_q"), spec.pop("s_k")
    causal = spec.pop("causal", True)
    static = spec.pop("static", True)
    plans = block_schedule(s_q, s_k, spec["block_q"], spec["block_k"], causal)
    for plan in plans.values():
        assert plan.static == static
        assert plan.steps_masked
        assert bool(plan.steps_unmasked) == (static or not causal)
    assert max(len(steps) for steps in plans["fwd"].rows) > 1
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (1, s_q, 2, 64), dtype=jnp.float32)
    k = jax.random.normal(keys[1], (1, s_k, 2, 64), dtype=jnp.float32)
    v = jax.random.normal(keys[2], (1, s_k, 2, 64), dtype=jnp.float32)

    def loss(q, k, v, **how):
        out = flash_attention(q, k, v, causal=causal, **how)
        return jnp.sum(out ** 2), out

    (_, out), g1 = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v, interpret=True, **spec)
    (_, ref), g2 = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_attention_counts_its_steps():
    """Building the kernels adds the schedule's step counts to the
    process's counters (per lowering, not per run)."""
    from ray_tpu._private import device_profiler

    q, k, v = _make_qkv(S=512)
    how = dict(causal=True, interpret=True, block_q=128, block_k=256)
    plans = block_schedule(512, 512, 128, 256, True)
    before = device_profiler.snapshot()["counters"]
    jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, **how)))(q)
    after = device_profiler.snapshot()["counters"]
    for name, field in (("flash.steps_unmasked", "steps_unmasked"),
                        ("flash.steps_masked", "steps_masked")):
        assert after[name] - before.get(name, 0) == sum(
            getattr(plans[kernel], field) for kernel in ("fwd", "dq", "dkv"))
