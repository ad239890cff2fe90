"""Pallas-op tests (interpret mode on CPU; the oracle is plain JAX)."""

import hashlib
import itertools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import eva
from ray_tpu.ops.flash_attention import (
    _LOOP_BODY, _STATIC_BUDGET, _STATIC_STEPS, _SUB, CAUSAL, DIAGONAL, Band,
    BlockDiffusion, EvaWindows, SharedRows,
    SlidingWindow, Triangle, _clamp_block, _reference_attention,
    block_schedule, flash_attention)


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


@pytest.mark.parametrize("heads, kv_heads", [(4, 4), (4, 2), (8, 2), (16, 2),
                                             (4, 1)],
                         ids=["group-1", "group-2", "group-4", "group-8",
                              "one-kv-head"])
def test_flash_attention_gqa(heads, kv_heads):
    """K and V at the KV heads' count: query head h reads KV head h //
    group, and dk and dv come back at the KV heads' count, each the sum of
    its group's."""
    q, k, v = _make_qkv(H=heads, kv_heads=kv_heads, D=32, seed=heads)

    def loss(q, k, v, **how):
        out = flash_attention(q, k, v, causal=True, **how)
        return jnp.sum(out ** 2), out

    (_, out), g1 = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v, interpret=True, block_q=64, block_k=64)
    (_, ref), g2 = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for a, b in zip(g1, g2):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


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


def _dense_block_diffusion(length, block):
    """bool [2L, 2L], written out from the rule's words: same half and
    block, or a clean key of an earlier block."""
    pos = np.arange(2 * length)
    clean, blk = pos >= length, (pos % length) // block
    return ((clean[:, None] == clean[None]) & (blk[:, None] == blk[None])) \
        | (clean[None] & (blk[None] < blk[:, None]))


def _dense_window(s_q, s_k, window):
    """bool [s_q, s_k], written out from the rule's words: row r stands at
    position r + s_k - s_q and sees the `window` keys that end there."""
    at = np.arange(s_q)[:, None] + (s_k - s_q)
    key = np.arange(s_k)[None, :]
    return (key <= at) & (key > at - window)


def _mask(s_q, s_k, causal, rows, cols):
    """The dense mask of `causal` (True, False, a block-diffusion rule or a
    window) over a rows x cols padded area."""
    r = np.arange(rows)[:, None]
    c = np.arange(cols)[None, :]
    m = (r < s_q) & (c < s_k)
    if isinstance(causal, SlidingWindow):
        rule = np.zeros((rows, cols), bool)
        rule[:s_q, :s_k] = _dense_window(s_q, s_k, causal.window)
        return m & rule
    if isinstance(causal, BlockDiffusion):
        rule = np.zeros((rows, cols), bool)
        rule[:s_q, :s_k] = _dense_block_diffusion(causal.length, causal.block)
        return m & rule
    if isinstance(causal, EvaWindows):
        rule = np.zeros((rows, cols), bool)
        rule[:s_q, :s_k] = _dense_eva(causal.length, causal.window,
                                      causal.chunk)
        return m & rule
    return m & (c <= r + (s_k - s_q)) if causal else m


# (s_q, s_k, block_q, block_k, causal): executed/needed bound of fwd/dq, of dk/dv
_SCHEDULES = {
    # the four diagonal tiles in two halves, on 12 of their 16 sub-tiles
    # (PR 51): 1.25 as whole tiles
    "s2048-default": ((2048, 2048, None, None, True), 1.125, 1.125),
    # causal steps are cut to the owned block: 512 queries, 1,024 keys; a
    # tile that is not square (the owned block of 1,024 over steps of 512)
    # runs whole, and so does every tile of a plan in a loop
    "s2048-512x1024": ((2048, 2048, 512, 1024, True), 1.125, 1.5),
    "s2048-1024x512": ((2048, 2048, 1024, 512, True), 1.5, 1.125),
    "s2048-256x512": ((2048, 2048, 256, 512, True), 1.0625, 1.25),
    "s2048-256x256": ((2048, 2048, 256, 256, True), 1.0625, 1.125),
    "s2048-noncausal": ((2048, 2048, 512, 1024, False), 1.0, 1.0),
    "s4096-default": ((4096, 4096, None, None, True), 1.0625, 1.125),
    "s8192-default": ((8192, 8192, None, None, True), 1.0625, 1.0625),
    "cross-128-over-384": ((128, 384, 128, 128, True), 1.2, 1.2),
    "cross-64-over-128": ((64, 128, None, None, True), 1.33, 1.33),
    "more-queries-than-keys": ((384, 128, 128, 128, True), 2.0, 2.0),
    "s320-padded": ((320, 320, 128, 128, True), 1.92, 1.92),
    "s320-padded-noncausal": ((320, 320, 128, 256, False), 1.92, 1.92),
    "s192-on-128": ((192, 192, 128, 128, True), 2.66, 2.66),
    "s100-one-block": ((100, 100, None, None, True), 2.0, 2.0),
    "s128-one-block": ((128, 128, None, None, False), 1.0, 1.0),
    # block diffusion over [x_t ; x_0]: 24 of the 64 tiles of 512 x 512,
    # the 4 x_t diagonal ones on their four 128-wide sub-tiles, the 4 x_0
    # and the 4 x_t -> x_0 diagonal ones on 12 of their 16: 19 / 24 of
    # 1.4971
    "bd-l2048-b4": ((4096, 4096, None, None, BlockDiffusion(2048, 4)),
                    1.1853, 1.1853),
    # a block as wide as the sub-tile, and one wider (no diagonal step; at
    # 256 the x_0 diagonal tiles keep both blocks on their diagonal whole,
    # scores on both sides: whole tiles; the x_t -> x_0 ones triangles)
    "bd-l2048-b128": ((4096, 4096, None, None, BlockDiffusion(2048, 128)),
                      1.12, 1.12),
    "bd-l2048-b256": ((4096, 4096, None, None, BlockDiffusion(2048, 256)),
                      1.28, 1.28),
    "bd-l1024-b32-256": ((2048, 2048, 256, 256, BlockDiffusion(1024, 32)),
                         1.22, 1.22),
    # x_t ends inside a tile: that tile holds x_0 keys too, so is no
    # diagonal step (the first x_t tile is the only one)
    "bd-l640-b4-tile-cuts-the-halves": (
        (1280, 1280, None, None, BlockDiffusion(640, 4)), 3.34, 3.34),
    "bd-l512-b4-128": ((1024, 1024, 128, 128, BlockDiffusion(512, 4)),
                       1.49, 1.49),
    # a block that does not divide the tile, a length that is no tile multiple
    "bd-l192-b3-128": ((384, 384, 128, 128, BlockDiffusion(192, 3)),
                       3.51, 3.51),
    "bd-l100-b4-padded": ((200, 200, None, None, BlockDiffusion(100, 4)),
                          6.31, 6.31),
    "bd-l1280-b4-loops": ((2560, 2560, 128, 128, BlockDiffusion(1280, 4)),
                          1.2, 1.2),
    # a window: 31 of the 256 tiles of 512 x 512 hold its 4,063,488 kept
    # scores (2.0 as whole tiles); 15 rows of two run as ONE band step, each
    # 128-row group on 5 sub-tiles of their 8
    "swa-w512-s8192": ((8192, 8192, None, None, SlidingWindow(512)),
                       1.26, 1.26),
    # band steps elsewhere: a window that is not the tile (3 and 2 sub-tiles
    # a group), one that divides nothing (6 sub-tiles, both ends cut), a
    # padded last block, s_q != s_k (a multiple of the sub-tile apart; 64
    # apart a group's run is one sub-tile longer, and the key groups of
    # dk/dv keep runs of different lengths: whole tiles there)
    "swa-w256-s2048-band": ((2048, 2048, None, None, SlidingWindow(256)),
                            1.6, 1.6),
    "swa-w128-s1024-256x256-band": (
        (1024, 1024, 256, 256, SlidingWindow(128)), 2.0, 2.0),
    "swa-w600-s2048-band": ((2048, 2048, None, None, SlidingWindow(600)),
                            1.38, 1.38),
    "swa-w256-s900-256x256-padded-band": (
        (900, 900, 256, 256, SlidingWindow(256)), 1.74, 1.74),
    "swa-w256-cross-512-over-768-band": (
        (512, 768, 256, 256, SlidingWindow(256)), 1.5, 1.5),
    "swa-w256-cross-512-over-832-band": (
        (512, 832, 256, 256, SlidingWindow(256)), 2.0, 2.5),
    # no band: a run longer than two steps (9 sub-tiles at 512), and rows
    # of 128 (one group: nothing to stagger); a row's trailing tile (a
    # strict upper triangle) and its own are triangles of either hand
    "swa-w1024-s4096-no-band": ((4096, 4096, None, None, SlidingWindow(1024)),
                                1.25, 1.25),
    # windows and lengths that do not divide each other, blocks that differ
    "swa-w1000-s2048-256x512": ((2048, 2048, 256, 512, SlidingWindow(1000)),
                                1.15, 1.53),
    "swa-w100-s320-padded": ((320, 320, 128, 128, SlidingWindow(100)),
                             3.03, 3.03),
    "swa-w200-cross-128-over-384": ((128, 384, 128, 128, SlidingWindow(200)),
                                    1.92, 1.92),
    "swa-w50-more-queries-than-keys": (
        (384, 128, 128, 128, SlidingWindow(50)), 3.17, 3.17),
    # loop plans whose rows of ONE shape share an unrolled branch (PR 58): 8
    # of 16 rows, 7 tiles with no mask, then the trailing and their own tile
    # whole under the mask; a padded last block among them; 12 of 20 rows in
    # tiles of one sub-tile. What runs is what the loop ran
    "swa-w2048-s4096-256x256-shared-rows": (
        (4096, 4096, 256, 256, SlidingWindow(2048)), 1.125, 1.125),
    "swa-w1024-s3000-256x256-shared-rows-padded": (
        (3000, 3000, 256, 256, SlidingWindow(1024)), 1.286, 1.286),
    "swa-w1024-s2560-128x128-shared-rows": (
        (2560, 2560, 128, 128, SlidingWindow(1024)), 1.125, 1.125),
    # loop plans whose rows run their whole tiles in bodies of 4 and of 2
    # steps with no mask (PR 60): more keys than queries, a padded last
    # block, and `EvaWindows`, whose rows' whole tiles are not one range
    "cross-640-over-1408-128x128-loop-bodies": (
        (640, 1408, 128, 128, True), 1.06, 1.06),
    "s1200-128x128-padded-loop-bodies": (
        (1200, 1200, 128, 128, True), 1.26, 1.26),
    "eva-l2560-w1280-c16-128x128-loop-bodies": (
        (2560, 2720, 128, 128, EvaWindows(2560, 1280, 16)), 1.32, 1.32),
}


# the cases whose loop plans run steps in bodies: how many, forward / dq and
# dk/dv
_LOOP_BODIES = {
    "s8192-default": (112, 112),
    "s4096-default": (0, 24),
    "s2048-256x256": (0, 24),      # dk/dv: 36 steps in rows of up to 8
    "bd-l1280-b4-loops": (80, 90),
    # the edge rows of a plan whose interior rows share a branch
    "swa-w2048-s4096-256x256-shared-rows": (24, 24),
    "swa-w1024-s3000-256x256-shared-rows-padded": (4, 2),
    "swa-w1024-s2560-128x128-shared-rows": (24, 24),
    "cross-640-over-1408-128x128-loop-bodies": (38, 32),
    "s1200-128x128-padded-loop-bodies": (40, 32),
    "eva-l2560-w1280-c16-128x128-loop-bodies": (64, 64),
}


# the cases whose loop plans hold rows that share a branch: how many rows
_SHARED = {
    "swa-w2048-s4096-256x256-shared-rows": 8,
    "swa-w1024-s3000-256x256-shared-rows-padded": 8,
    "swa-w1024-s2560-128x128-shared-rows": 12,
}


# the cases whose plans hold band steps: how many, forward / dq and dk/dv
_BANDS = {
    "swa-w512-s8192": (15, 15),
    "swa-w256-s2048-band": (3, 3),
    "swa-w128-s1024-256x256-band": (3, 3),
    "swa-w600-s2048-band": (2, 2),
    "swa-w256-s900-256x256-padded-band": (3, 3),
    "swa-w256-cross-512-over-768-band": (2, 1),
    "swa-w256-cross-512-over-832-band": (2, 0),
}


# the cases whose plans hold triangle steps: how many, in the forward's, dq's
# and dk/dv's (a plan in a loop, a tile that is not square or is one sub-
# tile, a tile that keeps scores on both sides of its sub-tile diagonal: none)
_TRIANGLES = {
    "s2048-default": (4, 4, 4),
    "s2048-512x1024": (4, 4, 0),
    "s2048-1024x512": (0, 0, 4),
    "s2048-256x512": (8, 8, 0),
    "s2048-256x256": (8, 8, 0),
    "s4096-default": (8, 8, 0),
    "bd-l2048-b4": (8, 8, 8),
    "bd-l2048-b128": (8, 8, 8),
    "bd-l2048-b256": (4, 4, 4),
    "bd-l1024-b32-256": (8, 8, 8),
    "bd-l640-b4-tile-cuts-the-halves": (4, 4, 4),
    "swa-w512-s8192": (1, 1, 1),
    "swa-w600-s2048-band": (2, 2, 2),
    "swa-w256-s2048-band": (1, 1, 1),
    "swa-w128-s1024-256x256-band": (1, 1, 1),
    "swa-w256-s900-256x256-padded-band": (1, 1, 1),
    "swa-w256-cross-512-over-768-band": (0, 0, 2),
    "swa-w256-cross-512-over-832-band": (0, 0, 4),
    "swa-w1024-s4096-no-band": (14, 14, 14),
    "swa-w1000-s2048-256x512": (12, 12, 0),
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
    for kernel, bound in zip(("fwd", "dq", "dkv"),
                             (bounds[0], bounds[0], bounds[1])):
        plan = plans[kernel]
        name = "dkv" if kernel == "dkv" else "fwd"   # which axis it owns
        rows = max(t[0] + t[1] for t in plan.tiles)
        cols = max(t[2] + t[3] for t in plan.tiles)
        if name == "fwd":   # a band step may end inside the last whole step
            cols = -(-cols // plan.width) * plan.width
        else:
            rows = -(-rows // plan.width) * plan.width
        mask = _mask(s_q, s_k, causal, rows, cols)
        painted = np.zeros((rows, cols), dtype=np.int32)
        stood_for = 0   # whole tiles the band steps run in place of
        for q0, nq, k0, nk, masked in plan.tiles:
            if isinstance(masked, Band):
                # a row's only step, unrolled: each 128-row group of the
                # owned block against its own run of sub-tiles, which start
                # a sub-tile apart and span the tile's other side
                assert plan.static
                own0, own, walked0, span = (
                    (q0, nq, k0, nk) if name == "fwd" else (k0, nk, q0, nq))
                assert own == (block_q if name == "fwd" else block_k)
                assert masked.shift == walked0 - own0 and masked.run * _SUB \
                    == span - own + _SUB <= 2 * plan.width
                assert own % plan.width == 0
                stood_for += -(-(walked0 + span) // plan.width) \
                    - walked0 // plan.width - 1
                for a in range(0, own, _SUB):
                    group = slice(own0 + a, own0 + a + _SUB)
                    run = slice(walked0 + a, walked0 + a + masked.run * _SUB)
                    painted[(group, run) if name == "fwd"
                            else (run, group)] += 1
                continue
            # the owned block, and a step of the plan's width along the other
            assert (nq, nk) == ((block_q, plan.width) if name == "fwd"
                                else (plan.width, block_k))
            sub_tiled = nq == nk and nq % _SUB == 0 and nq > _SUB
            if masked == DIAGONAL:
                # runs its aligned diagonal sub-tiles, unrolled only
                assert plan.static and sub_tiled
                for a in range(0, nq, _SUB):
                    painted[q0 + a:q0 + a + _SUB, k0 + a:k0 + a + _SUB] += 1
            elif isinstance(masked, Triangle):
                # unrolled only: each half of the OWNED block's 128-row
                # groups is one step, against the walked axis up to the
                # half's own end (leading) or from its start on; the
                # quarter of the tile that is not run holds no score (the
                # coverage below)
                assert plan.static and sub_tiled
                own0, walked0 = (q0, k0) if name == "fwd" else (k0, q0)
                half = (nq // _SUB + 1) // 2 * _SUB
                for lo, hi in ((0, half), (half, nq)):
                    run = slice(walked0, walked0 + hi) if masked.leading \
                        else slice(walked0 + lo, walked0 + nq)
                    rows_ = slice(own0 + lo, own0 + hi)
                    painted[(rows_, run) if name == "fwd"
                            else (run, rows_)] += 1
            else:
                painted[q0:q0 + nq, k0:k0 + nk] += 1
                if masked and plan.static and sub_tiled and causal:
                    # a masked step that is not diagonal keeps a score off
                    # its diagonal sub-tiles
                    off = mask[q0:q0 + nq, k0:k0 + nk].copy()
                    for a in range(0, nq, _SUB):
                        off[a:a + _SUB, a:a + _SUB] = False
                    assert off.any(), (q0, k0)
            if causal:
                # at most square: the diagonal never cuts a step twice
                # the size of what it leaves visible
                assert plan.width <= max(block_q, block_k)
                if plan.static:
                    # a tile the rule keeps nothing of is no step
                    assert mask[q0:q0 + nq, k0:k0 + nk].any(), (q0, k0)
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
        # unrolled by the longest row (forward; dq runs its plan) or by the
        # plan's steps in all (dk/dv)
        measure, budget = _STATIC_BUDGET[name]
        assert plan.static == (
            measure(len(r) for r in plan.rows) <= budget
            and sum(len(r) for r in plan.rows) <= _STATIC_STEPS)
        assert plan.steps_skipped == len(plan.rows) * (
            (cols if name == "fwd" else rows) // plan.width) \
            - len(plan.tiles) - stood_for
        assert [t[4] for t in plan.tiles] == [
            masked for row in plan.rows for _, masked in row]
        # a loop plan's rows of one shape, several and half its steps or
        # more, share a branch, each step masked only if it needs it, and
        # then whole; the loop's other rows run every step under the mask
        assert (plan.shared is not None) == (case in _SHARED)
        if plan.shared:
            assert not plan.static
            assert len(plan.shared.rows) == _SHARED[case]
            assert 2 * plan.steps_shared >= len(plan.tiles)
            for i, row in enumerate(plan.rows):
                if i in plan.shared.rows:
                    assert row == tuple(sorted(
                        (i * plan.shared.stride + at, m)
                        for at, m in plan.shared.steps))
                    assert [m for _, m in plan.shared.steps] == sorted(
                        m for _, m in row)
        # the loop's rows: the whole tiles that fill bodies of a size lead,
        # with no mask; every other step under it
        assert plan.steps_loop_body == _LOOP_BODIES.get(
            case, (0, 0))[name == "dkv"]
        if not plan.static:
            for i, row in enumerate(plan.rows):
                if plan.shared and i in plan.shared.rows:
                    continue
                lead = sum(k * n for k, n in zip(
                    plan.body, plan.bodies[i] if plan.body else ()))
                assert [m for _, m in row] \
                    == [False] * lead + [True] * (len(row) - lead)
                whole = [j for j, m in row[:lead]]
                assert whole == sorted(whole)
        tiles = iter(plan.tiles)
        for i, row in enumerate(plan.rows):
            # unrolled: a step is masked only if a score in it is not valid
            for q0, nq, k0, nk, masked in itertools.islice(tiles, len(row)):
                if not (plan.static
                        or plan.shared and i in plan.shared.rows):
                    continue
                real = (mask[q0:q0 + nq, k0:k0 + nk] if name == "fwd" else
                        mask[q0:q0 + nq, k0:min(k0 + nk, s_k)])
                assert bool(masked) == (
                    not real[:s_q - q0].all() if name == "fwd"
                    else not real.all()), (q0, k0)
        assert plan.steps_unmasked == sum(not t[4] for t in plan.tiles)
        assert plan.steps_masked == sum(bool(t[4]) for t in plan.tiles)
        assert plan.steps_diagonal == sum(
            t[4] == DIAGONAL for t in plan.tiles)
        if not isinstance(causal, BlockDiffusion):
            # CAUSAL's diagonal tiles keep 10 of their 16 sub-tiles
            assert plan.steps_diagonal == 0
        assert plan.steps_band == sum(
            isinstance(t[4], Band) for t in plan.tiles) == _BANDS.get(
                case, (0, 0))[name == "dkv"]
        assert plan.steps_triangle == sum(
            isinstance(t[4], Triangle) for t in plan.tiles) == _TRIANGLES.get(
                case, (0, 0, 0))[("fwd", "dq", "dkv").index(kernel)]
        np.testing.assert_allclose(plan.executed_over_needed,
                                   painted.sum() / mask.sum())
        assert plan.executed_over_needed <= bound + 1e-9, kernel


def test_block_schedule_starting_point():
    """The schedule before PR 26 (steps of block_q x block_k up to the
    diagonal, 512 x 1024) executed 1.5x the causal half at S 2048; square
    steps 1.25x (PR 26); with the four diagonal tiles in two halves, on 12
    of their 16 sub-tiles (PR 51), the defaults execute 1.125x in all three
    kernels (1.0625x would be the 10 that hold scores)."""
    s, block_q, block_k = 2048, 512, 1024
    old = sum(-(-(qi + 1) * block_q // block_k) * block_q * block_k
              for qi in range(s // block_q))
    assert old / _mask(s, s, True, s, s).sum() == pytest.approx(1.5, abs=1e-3)
    for plan in block_schedule(s, s, *_default_blocks(s, s), True).values():
        assert plan.executed_over_needed == pytest.approx(1.125, abs=1e-3)
        # six of the ten steps a head lie wholly below the diagonal, the
        # four on it are triangles
        assert (plan.steps_unmasked, plan.steps_masked,
                plan.steps_triangle) == (6, 4, 4)


# Shapes where a grid row runs several steps, some unmasked and some masked:
# plans short enough to unroll (`static`: at most 8 steps a row in the
# forward and dq, 28 steps in all in dk/dv, each step masked or not by
# itself) and longer ones (ONE loop a grid row over the plan's table: masked
# throughout if any step is).
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
    # 6 steps a row, 21 in all: dk/dv unrolled too
    "s768-128x128": dict(s_q=768, s_k=768, block_q=128, block_k=128),
    # 8 steps a row, 36 in all: the forward and dq unrolled, dk/dv in a loop
    "s1024-128x128-loops": dict(s_q=1024, s_k=1024, block_q=128, block_k=128,
                                static=("fwd", "dq")),
    "s704-128x128-noncausal-loops": dict(s_q=704, s_k=704, block_q=128,
                                         block_k=128, causal=False,
                                         static=("fwd", "dq")),
    # 9 and 10 steps a row: all three kernels in loops
    "s1152-128x128-loops": dict(s_q=1152, s_k=1152, block_q=128,
                                block_k=128, static=()),
    "s1216-128x128-noncausal-loops": dict(s_q=1216, s_k=1216, block_q=128,
                                          block_k=128, causal=False,
                                          static=()),
    # loops whose rows run their whole tiles in bodies of 4 and of 2 steps
    # (PR 60): more keys than queries (every row holds 6 or more), and a
    # padded last block (dk/dv's last query tile is under the mask in every
    # row: rows of 0 to 8 whole tiles, none and one body of each size)
    "cross-640-over-1408-loop-bodies": dict(s_q=640, s_k=1408, block_q=128,
                                            block_k=128, static=()),
    "s1200-128x128-padded-loop-bodies": dict(s_q=1200, s_k=1200, block_q=128,
                                             block_k=128, static=()),
}


@pytest.mark.parametrize("case", sorted(_MIXED))
def test_flash_attention_interior_and_edge_steps(case):
    """Forward and gradients against the oracle where a grid row runs
    unmasked steps and masked ones, unrolled or in loops."""
    spec = dict(_MIXED[case])
    s_q, s_k = spec.pop("s_q"), spec.pop("s_k")
    causal = spec.pop("causal", True)
    static = spec.pop("static", ("fwd", "dq", "dkv"))
    plans = block_schedule(s_q, s_k, spec["block_q"], spec["block_k"], causal)
    for name, plan in plans.items():
        assert plan.static == (name in static)
        assert plan.steps_masked
        # a loop's steps with no mask are those of its bodies
        assert plan.steps_unmasked == plan.steps_loop_body or plan.static
        assert plan.steps_unmasked
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


@pytest.mark.parametrize("rule", [True, BlockDiffusion(256, 4),
                                  SlidingWindow(100)],
                         ids=["causal", "block-diffusion", "window"])
def test_flash_attention_counts_its_steps(rule):
    """Building the kernels adds the schedule's step counts to the
    process's counters (per lowering, not per run); a diagonal step and a
    band step are masked ones too."""
    from ray_tpu._private import device_profiler

    q, k, v = _make_qkv(B=2, S=512, H=4, kv_heads=2)
    how = dict(causal=rule, interpret=True, block_q=256, block_k=256)
    plans = block_schedule(512, 512, 256, 256, rule)
    # under the rule: the one x_t diagonal tile, in each of three kernels
    assert sum(p.steps_diagonal for p in plans.values()) == (
        3 if isinstance(rule, BlockDiffusion) else 0)
    # under the window: the one row of two tiles, each group's 2 sub-tiles
    # of their 4
    assert sum(p.steps_band for p in plans.values()) == (
        3 if isinstance(rule, SlidingWindow) else 0)
    # a causal call's two diagonal tiles; the x_0 and the x_t -> x_0 tile;
    # a window's first row, its own tile alone (the second is the band)
    assert [p.steps_triangle for p in plans.values()] == [
        1 if isinstance(rule, SlidingWindow) else 2] * 3
    before = device_profiler.snapshot()["counters"]
    jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, **how)))(q)
    after = device_profiler.snapshot()["counters"]
    # a call under the window rule, and no other, counts itself
    assert after.get("flash.window_calls", 0) - before.get(
        "flash.window_calls", 0) == isinstance(rule, SlidingWindow)
    for name, field in (("flash.steps_unmasked", "steps_unmasked"),
                        ("flash.steps_masked", "steps_masked"),
                        ("flash.steps_diagonal", "steps_diagonal"),
                        ("flash.steps_band", "steps_band"),
                        ("flash.steps_triangle", "steps_triangle"),
                        ("flash.tiles_skipped", "steps_skipped")):
        assert after[name] - before.get(name, 0) == sum(
            getattr(plans[kernel], field) for kernel in ("fwd", "dq", "dkv"))
    # the forward's and dq's (batch, head) grid rows, and those of them that
    # fetch a K and V of their own: 2 KV heads under 4 query heads
    assert after["flash.head_rows"] - before.get("flash.head_rows", 0) \
        == 2 * 2 * 4
    assert after["flash.kv_head_fetches"] - before.get(
        "flash.kv_head_fetches", 0) == 2 * 2 * 2


def test_block_diffusion_schedule_at_the_cell_shape():
    """L 2,048, block 4, tiles of 512 over the 4,096-long concatenation: 24
    of 64 tiles run (10 block-causal x_0 tiles, 10 x_t -> x_0 tiles, 4 x_t
    diagonal tiles that keep 2,048 of 262,144 scores each), half of them
    with no mask; the rule keeps L^2 + L x block scores, HALF of what a
    causal call at S 4,096 keeps; an x_t row tile visits x_0 tiles 0..i and
    then its own x_t tile, which is no contiguous range. The EIGHT masked
    tiles that are not `DIAGONAL`, the four x_0 diagonal tiles (block-
    causal, `k_blk <= q_blk`) and the four x_t -> x_0 diagonal tiles
    (`k_blk < q_blk`), keep 10 of their 16 sub-tiles and are triangles,
    run in two halves on 12."""
    length, block, tile = 2048, 4, 512
    rule = BlockDiffusion(length, block)
    plans = block_schedule(2 * length, 2 * length, tile, tile, rule)
    assert rule.needed(2 * length, 2 * length) \
        == length * length + length * block == 4_202_496
    assert CAUSAL.needed(2 * length, 2 * length) \
        == pytest.approx(2 * 4_202_496, rel=2e-3)
    dense = _dense_block_diffusion(length, block)
    assert dense.sum() == 4_202_496
    for name, plan in plans.items():
        assert len(plan.tiles) == 24 and plan.steps_skipped == 40
        # the x_t diagonal tiles run their four 128-wide sub-tiles, the
        # eight triangles 12 of 16: 19 / 24 of the 1.497 that 24 whole
        # tiles are
        assert plan.executed_over_needed == pytest.approx(
            19 * tile * tile / 4_202_496) == pytest.approx(1.1852, abs=1e-4)
        by_kind = {"x0": 0, "xt_to_x0": 0, "xt_diagonal": 0}
        hand = Triangle(name != "dkv")
        for q0, nq, k0, nk, masked in plan.tiles:
            assert dense[q0:q0 + nq, k0:k0 + nk].any()
            if q0 >= length:
                assert k0 >= length            # x_0 never sees x_t
                by_kind["x0"] += 1
            elif k0 >= length:
                by_kind["xt_to_x0"] += 1
            else:
                assert q0 == k0                # x_t sees its own block only
                assert dense[q0:q0 + nq, k0:k0 + nk].sum() == length
                by_kind["xt_diagonal"] += 1
            assert (masked == DIAGONAL) == (k0 < length)
            # the tiles on the diagonal of each clean quadrant
            assert (masked == hand) == (k0 >= length
                                        and q0 % length == k0 - length)
        assert by_kind == {"x0": 10, "xt_to_x0": 10, "xt_diagonal": 4}
        # all three unrolled, each step masked only if it needs it
        assert plan.static and plan.steps_diagonal == 4
        assert (plan.steps_unmasked, plan.steps_masked,
                plan.steps_triangle) == (12, 12, 8)
    fwd, dkv = plans["fwd"], plans["dkv"]
    assert [len(r) for r in fwd.rows] == [2, 3, 4, 5, 1, 2, 3, 4]
    assert [j for j, _ in fwd.rows[2]] == [2, 4, 5, 6]   # own tile, x_0 0..2
    assert fwd.rows[2][0] == (2, DIAGONAL)
    # dk/dv: an x_0 key tile is walked by up to 8 query tiles, an x_t key
    # tile by its own query tile alone; 24 steps in all, so unrolled
    assert [len(r) for r in dkv.rows] == [1, 1, 1, 1, 8, 6, 4, 2]
    assert dkv.rows[0] == ((0, DIAGONAL),)
    assert [j for j, _ in dkv.rows[4]] == list(range(8))
    assert dkv.rows[5] == ((1, Triangle(False)), (2, False), (3, False),
                           (5, Triangle(False)), (6, False), (7, False))
    for q0 in (512, 512 + length):   # both kinds, sub-tile by sub-tile
        assert [[rule.tile(q0 + a, 128, length + 512 + b, 128)
                 for b in range(0, 512, 128)] for a in range(0, 512, 128)] \
            == [[(True, True)] * g + [(True, False)] + [(False, False)]
                * (3 - g) for g in range(4)]


# what dk/dv's budget is for: the plan's steps in all (a head's, at 512 x
# 512), not its longest row
_DKV_PLANS = {
    "causal-s2048": (2048, True, 10, True),
    "causal-s3072": (3072, True, 21, True),
    "block-diffusion-l2048": (4096, BlockDiffusion(2048, 4), 24, True),
    "causal-s3584": (3584, True, 28, True),
    "causal-s4096": (4096, True, 36, False),     # collapsed unrolled, PR 26
    "block-diffusion-l3072": (6144, BlockDiffusion(3072, 4), 48, False),
    "causal-s8192": (8192, True, 136, False),
    # 31 whole tiles, 3 over the budget, as 15 band steps and one tile
    "window-512-s8192": (8192, SlidingWindow(512), 16, True),
    # twice as long: 31 band steps and a tile are over it, so 63 whole tiles;
    # 31 of its rows are ONE row (their own tile and the trailing one) and
    # share an unrolled branch (PR 58)
    "window-512-s16384": (16384, SlidingWindow(512), 63, False),
}


@pytest.mark.parametrize("case", sorted(_DKV_PLANS))
def test_dkv_is_unrolled_under_a_budget_of_the_plans_total_steps(case):
    s, rule, steps, static = _DKV_PLANS[case]
    plans = block_schedule(s, s, *_default_blocks(s, s), rule)
    dkv = plans["dkv"]
    assert len(dkv.tiles) == steps
    assert dkv.static == static == (steps <= _STATIC_BUDGET["dkv"][1])
    # the longest row says nothing: 8 in a plan that is unrolled and in one
    # that is not
    if case in ("block-diffusion-l2048", "causal-s4096"):
        assert max(map(len, dkv.rows)) == 8
    if not static:
        # loops on whole tiles, masked throughout but for the whole tiles
        # that fill a loop's bodies (a window of one tile has none) and the
        # rows of one shape where they are the plan's majority
        assert dkv.steps_unmasked == dkv.steps_loop_body == {
            "causal-s4096": 24, "block-diffusion-l3072": 30,
            "causal-s8192": 112, "window-512-s16384": 0}[case]
        assert dkv.steps_diagonal == 0 == dkv.steps_band
        assert (dkv.shared is not None) == (case == "window-512-s16384")
        if dkv.shared:   # its steps are masked ones in the loop's rows too
            assert dkv.shared == SharedRows(
                tuple(range(31)), 1, ((0, True), (1, True)))
            assert dkv.rows[31] == ((31, True),)
        assert dkv.steps_shared == (62 if dkv.shared else 0)
        assert dkv.steps_triangle == 0
        lead = 1 + len(dkv.body)   # the steps one at a time, the bodies
        assert dkv.table[-1, 0] + np.dot(dkv.table[-1, 1:lead], dkv.body) \
            == len(dkv.rows[-1])
    # forward and dq keep their cap on the longest row, under the cap on a
    # plan's steps in all (block diffusion at L 3,072: rows of 8, 48 steps)
    assert plans["fwd"].static == (
        max(map(len, plans["fwd"].rows)) <= 8
        and len(plans["fwd"].tiles) <= _STATIC_STEPS)


# (length, block, tile[, q heads, kv heads, diagonal steps a plan]): the three
# kernels under the rule, in the Pallas interpreter, against the dense-mask
# oracle
_BLOCK_DIFFUSION = {
    "l512-b4-unrolled-and-loops": (512, 4, 128),
    "l192-b3-block-cuts-the-tile": (192, 3, 128),
    "l100-b4-padded": (100, 4, 128),
    "l320-b5-length-no-tile-multiple": (320, 5, 128),
    "l1280-b4-all-loops": (1280, 4, 128),
    "l64-b1": (64, 1, 64),
    # tiles wider than 128: an x_t diagonal tile whose blocks stay inside
    # its 128-wide diagonal sub-tiles is a DIAGONAL step, next to x_0's
    # diagonal tiles (block-causal: masked, and not diagonal-only)
    "diagonal-b4-tile512": (512, 4, 512, 2, 1, 1),
    "diagonal-b32-tile256": (512, 32, 256, 2, 1, 2),
    "diagonal-b128-as-wide-as-the-sub-tile": (512, 128, 256, 2, 1, 2),
    "diagonal-none-b256-wider-than-the-sub-tile": (512, 256, 512, 2, 1, 0),
    # tiles that hold both halves, and padding: the first x_t tile, and two
    # whose second sub-tile row or column is x_0 rows that see none of these
    # keys, or lies past the end
    "diagonal-l320-length-no-tile-multiple": (320, 4, 256, 2, 1, 3),
    "diagonal-gqa-8-to-1": (512, 4, 256, 8, 1, 2),
    # KV heads shared by groups of 1, 4 and 8 query heads
    "gqa-4-to-4-no-head-shared": (192, 3, 128, 4, 4, 0),
    "diagonal-gqa-8-to-2-groups-of-4": (256, 4, 256, 8, 2, 1),
    "gqa-16-to-2-groups-of-8": (64, 1, 64, 16, 2, 0),
    "diagonal-b3-block-cuts-the-sub-tile": (384, 3, 256, 2, 1, 0),
}


@pytest.mark.parametrize("case", sorted(_BLOCK_DIFFUSION))
def test_flash_attention_under_the_block_diffusion_rule(case):
    """Forward and the three gradients of the Pallas kernels (interpret
    mode) under `mask=BlockDiffusion(L, block)` against the oracle, which
    builds the DENSE [2L, 2L] mask; GQA; and the oracle's dense mask is the
    rule's words written out."""
    length, block, tile, heads, kv_heads, diagonal = (
        _BLOCK_DIFFUSION[case] + (4, 2, 0))[:6]
    rule, s = BlockDiffusion(length, block), 2 * length
    for plan in block_schedule(s, s, tile, tile, rule).values():
        assert plan.steps_diagonal == diagonal
        assert plan.static or not diagonal
    np.testing.assert_array_equal(
        rule.keep(np.arange(s)[:, None], np.arange(s)[None, :]),
        _dense_block_diffusion(length, block))
    q, k, v = _make_qkv(S=s, H=heads, kv_heads=kv_heads, D=32, seed=length)

    def loss(q, k, v, **how):
        out = flash_attention(q, k, v, mask=rule, **how)
        return jnp.sum(out ** 2), out

    (_, out), g1 = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v, interpret=True, block_q=tile, block_k=tile)
    (_, ref), g2 = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    # the oracle against attention written out with the dense mask
    rep = heads // kv_heads
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, axis=2)) \
        / 32 ** 0.5
    scores = jnp.where(_dense_block_diffusion(length, block), scores, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                      jnp.repeat(v, rep, axis=2))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("s_q, s_k, window", [
    (8192, 8192, 512), (300, 300, 77), (100, 260, 64), (260, 100, 64),
    (64, 64, 1), (64, 64, 1000)])
def test_sliding_window_rule_against_the_dense_mask(s_q, s_k, window):
    """`keep`, `needed` and `tile` (in closed form: no tile is built) against
    the dense mask, for windows and lengths that do not divide each other
    and for s_q != s_k; tiles of every alignment, some past the end."""
    rule, offset = SlidingWindow(window), s_k - s_q
    dense = _dense_window(s_q, s_k, window)
    np.testing.assert_array_equal(
        rule.keep(np.arange(s_q)[:, None] + offset, np.arange(s_k)[None]),
        dense)
    assert rule.needed(s_q, s_k) == dense.sum()
    if (s_q, window) == (8192, 512):
        assert rule.needed(s_q, s_k) == 4_063_488
        assert CAUSAL.needed(s_q, s_k) == 33_558_528
    size = 512 if s_q > 512 else 48
    padded = np.zeros((s_q + 2 * size, s_k + 2 * size), bool)
    for r in range(padded.shape[0]):   # positions past the end keep the rule
        padded[r] = rule.keep(np.full(padded.shape[1], r + offset),
                              np.arange(padded.shape[1]))
    for q0 in range(0, s_q, size // 3 * 2):
        for k0 in range(0, s_k, size // 2):
            for nq, nk in ((size, size), (size // 2, size), (1, 7)):
                kept = padded[q0:q0 + nq, k0:k0 + nk]
                assert rule.tile(q0 + offset, nq, k0, nk) == (
                    bool(kept.any()), bool(kept.all())), (q0, nq, k0, nk)


def test_sliding_window_schedule_at_the_cell_shape():
    """train-laguna-1chip's two calls a period, S 8,192 in tiles of 512.
    The window layers: a forward row's kept scores lie in its own tile (a
    lower triangle) and the one before it (the window's TRAILING tile, a
    strict upper triangle, which keeps scores off its diagonal sub-tiles
    and so is never `DIAGONAL`): 31 tiles, 2.0x the kept scores. Asked sub-
    tile by sub-tile each 128-row group keeps 5 of the 8 sub-tiles the two
    span, a sub-tile on from the group before, so the row is ONE band step
    (20 sub-tiles for 32; 1.25x), but for the first, which has no tile
    before it (dk/dv: the last key tile none after) and is a triangle (12
    sub-tiles for 16): 16 steps a plan, all three unrolled, dk/dv's 16
    under its budget of 28. The full layers run
    `CAUSAL` in loops throughout (rows of up to 16, 136 steps)."""
    rule = SlidingWindow(512)
    plans = block_schedule(8192, 8192, 512, 512, rule)
    for name, plan in plans.items():
        assert [len(r) for r in plan.rows] == [1] * 16
        assert (plan.steps_unmasked, plan.steps_masked, plan.steps_diagonal,
                plan.steps_band, plan.steps_triangle, plan.steps_skipped) \
            == (0, 16, 0, 15, 1, 225)
        assert plan.static
        assert plan.executed_over_needed == pytest.approx(
            (15 * 512 * 640 + 12 * 128 * 128) / 4_063_488) \
            == pytest.approx(1.258, abs=1e-3)
    assert plans["fwd"].rows[0] == ((0, Triangle(True)),)
    # ONE kind of band a plan, so one body for its fifteen rows: a query
    # row's keys start a tile before its own, a key row's queries with it
    assert plans["fwd"].rows[5] == ((4, Band(-512, 5)),)
    assert plans["fwd"].tiles[5] == (5 * 512, 512, 4 * 512, 1024,
                                     Band(-512, 5))
    assert plans["dkv"].rows[5] == ((5, Band(0, 5)),)
    assert plans["dkv"].tiles[5] == (5 * 512, 1024, 5 * 512, 512, Band(0, 5))
    assert {r[0][1] for r in plans["fwd"].rows[1:]} == {Band(-512, 5)}
    assert {r[0][1] for r in plans["dkv"].rows[:15]} == {Band(0, 5)}
    assert plans["dkv"].rows[15] == ((15, Triangle(False)),)
    some, every = rule.tile(5 * 512, 512, 4 * 512, 512)
    assert some and not every
    # the trailing tile's sub-tile (0, 1), above its diagonal, is kept whole
    assert rule.tile(5 * 512, 128, 4 * 512 + 128, 128) == (True, True)
    # group 1 of row 5 (queries from 2,688): keys 2,176 - 2,815, the first
    # and the last sub-tile cut, the three between whole, nothing beyond
    assert [rule.tile(5 * 512 + 128, 128, k0, 128)
            for k0 in range(4 * 512, 6 * 512, 128)] == [
        (False, False), (True, False), (True, True), (True, True),
        (True, True), (True, False), (False, False), (False, False)]
    causal = block_schedule(8192, 8192, 512, 512, True)
    for plan in causal.values():
        assert not plan.static and len(plan.tiles) == 136
        assert max(map(len, plan.rows)) == 16 and plan.steps_skipped == 120
        assert plan.steps_band == 0 == plan.steps_triangle


# what `block_schedule` returned before there was a band step (PR 47), a
# digest of every field it had, and the plan's triangle steps, for the
# forward, dq and dk/dv: the plans in LOOPS (S 8,192; dk/dv at S 4,096) are
# those plans still; the unrolled ones run their diagonal tiles as triangle
# steps since PR 51 and are pinned to that PR's
_PLANS_BEFORE_THE_BAND_STEP = {
    "causal-s2048": (2048, True,
        ("632862a2f69ef061", 4),
        ("632862a2f69ef061", 4),
        ("2e432b05487077ab", 4)),
    "causal-s4096": (4096, True,
        ("b55b3d926bff0b31", 8),
        ("b55b3d926bff0b31", 8),
        ("d71427cd32afbf3f", 0)),
    "causal-s8192": (8192, True, ("6a5cedcafb14667f", 0),
                     ("6a5cedcafb14667f", 0), ("337e6d5831d1eced", 0)),
    "block-diffusion-l2048": (4096, BlockDiffusion(2048, 4),
        ("10908d9460d329ae", 8),
        ("10908d9460d329ae", 8),
        ("193573bc7a39e7ee", 8)),
}


def _plan_digest(plan):
    return hashlib.sha256(repr((
        plan.width, plan.static, plan.tiles, plan.rows,
        plan.steps_unmasked, plan.steps_masked, plan.steps_diagonal,
        plan.steps_skipped, plan.executed_over_needed)).encode()
    ).hexdigest()[:16]


@pytest.mark.parametrize("case", sorted(_PLANS_BEFORE_THE_BAND_STEP))
def test_plans_without_a_band_are_what_they_were(case):
    """The plans of the other cells' calls (`CAUSAL` at S 2,048, 4,096 and
    8,192, `BlockDiffusion(2048, 4)` over 2 x 2,048) hold no band step: the
    rule's answers decide, and theirs fit no band (a causal group's runs
    all start at 0; a block-diffusion row's tiles are not one range, its
    x_t key tiles already run their diagonal alone). An unrolled plan
    differs from what the commit before the band step planned by its
    triangle steps alone (PR 51), a plan in a loop by the whole tiles that
    fill its loop's bodies, with no mask (PR 60)."""
    s, rule, *pinned = _PLANS_BEFORE_THE_BAND_STEP[case]
    plans = block_schedule(s, s, 512, 512, rule)
    for kernel, (digest, triangles) in zip(("fwd", "dq", "dkv"), pinned):
        plan = plans[kernel]
        assert plan.steps_band == 0
        assert plan.steps_triangle == triangles
        assert plan.static or not triangles
        assert not any(isinstance(t[4], Band) for t in plan.tiles)
        assert _plan_digest(plan) == digest


# (q heads, kv heads, window, tile, S[, keys, band steps in the forward's plan
# and in dk/dv's]): the cell's 48 / 8 and 64 / 8 scaled down; a window smaller
# than, equal to and larger than a tile, and one that divides nothing; S 512
# (384 for the padded case). Then the rows that run as ONE band step in all
# three kernels: the cell's own form (window = tile = 512 in sub-tiles of
# 128: 5 sub-tiles a group), a padded last block, s_q != s_k, a window that
# is no multiple of the sub-tile; and one whose run is too long for a step
# (whole tiles, the same answers)
_WINDOWS = {
    "6-to-1-window-below-the-tile": (6, 1, 50, 128, 512),
    "8-to-1-window-is-the-tile": (8, 1, 128, 128, 512),
    "6-to-1-window-above-the-tile": (6, 1, 300, 128, 512),
    "8-to-1-window-512-loops": (8, 1, 64, 64, 1024),
    "8-to-1-padded": (8, 1, 100, 256, 384),
    "6-to-1-window-past-the-sequence": (6, 1, 1000, 128, 256),
    "8-to-1-band-window-is-the-tile-512": (8, 1, 512, 512, 1536, 1536, 2, 2),
    "8-to-1-band-padded-last-block": (8, 1, 256, 256, 900, 900, 3, 3),
    "6-to-1-band-more-keys-than-queries": (6, 1, 256, 256, 512, 768, 2, 1),
    "8-to-1-band-window-divides-nothing": (8, 1, 200, 256, 768, 768, 2, 2),
    "8-to-1-no-band-run-too-long": (8, 1, 640, 256, 1024, 1024, 0, 0),
    # KV heads shared by groups of 1, 4 and 8 query heads, band steps in all
    "4-to-4-band-no-head-shared": (4, 4, 128, 256, 512, 512, 1, 1),
    "8-to-2-band-groups-of-4": (8, 2, 256, 256, 768, 768, 2, 2),
    "16-to-2-band-groups-of-8": (16, 2, 256, 256, 512, 512, 1, 1),
    # a window SEVERAL tiles wide under a group of 7 (SmallThinker's 28 / 4
    # x window 4,096 = 8 tiles, scaled down): whole tiles INSIDE the window,
    # never a band; the forward unrolled beside dk/dv in a loop, and all
    # three in loops over rows of 9 steps, as the cell's are
    "7-to-1-window-spans-4-tiles": (7, 1, 512, 128, 1024, 1024, 0, 0),
    "7-to-1-window-spans-8-tiles-loops": (7, 1, 1024, 128, 1280, 1280, 0, 0),
}


@pytest.mark.parametrize("case", sorted(_WINDOWS))
def test_flash_attention_under_the_sliding_window_rule(case):
    """Forward and the three gradients of the Pallas kernels (interpret
    mode) under `mask=SlidingWindow(w)` against `_reference_attention`,
    which builds the DENSE mask; GQA; band steps where the plan has them;
    and the oracle against attention written out with the mask in the
    rule's words."""
    heads, kv_heads, window, tile, s, s_k, *bands = (
        _WINDOWS[case] + _WINDOWS[case][4:5])[:8]
    rule = SlidingWindow(window)
    plans = block_schedule(s, s_k, tile, tile, rule)
    if bands:
        assert [plans[name].steps_band for name in ("fwd", "dq", "dkv")] \
            == [bands[0], bands[0], bands[1]]
    if case == "8-to-1-window-512-loops":
        assert not plans["dkv"].static and plans["fwd"].static
    if case == "6-to-1-window-past-the-sequence":   # then it is causal
        assert plans == block_schedule(s, s, tile, tile, True)
    if case.startswith("7-to-1-window-spans"):
        tiles = window // tile   # a row: a trailing triangle, whole, diagonal
        assert max(map(len, plans["fwd"].rows)) == tiles + 1
        assert not plans["dkv"].static
        assert plans["fwd"].static == (
            tiles + 1 <= _STATIC_BUDGET["fwd"][1]
            and len(plans["fwd"].tiles) <= _STATIC_STEPS)
        if plans["fwd"].static:   # whole tiles inside the window: no mask
            assert plans["fwd"].rows[tiles] == (
                (0, True), *((j, False) for j in range(1, tiles)),
                (tiles, True))
    q, k, v = _make_qkv(S=s_k, H=heads, kv_heads=kv_heads, D=32, seed=window)
    q = q[:, :s]

    def loss(q, k, v, **how):
        out = flash_attention(q, k, v, mask=rule, **how)
        return jnp.sum(out ** 2), out

    (_, out), g1 = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v, interpret=True, block_q=tile, block_k=tile)
    (_, ref), g2 = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    t = lambda x: jnp.swapaxes(x, 1, 2)  # noqa: E731
    rep = heads // kv_heads
    np.testing.assert_array_equal(ref, t(_reference_attention(
        t(q), t(jnp.repeat(k, rep, axis=2)), t(jnp.repeat(v, rep, axis=2)),
        rule, 32 ** -0.5)))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, axis=2)) \
        / 32 ** 0.5
    scores = jnp.where(_dense_window(s, s_k, window), scores, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                      jnp.repeat(v, rep, axis=2))
    np.testing.assert_allclose(np.asarray(ref), np.asarray(want), atol=2e-5)


# (q heads, kv heads, rule, tile, s_q, s_k, triangle steps in the forward's
# plan and in dk/dv's[, rotary channels]): the calls whose plans cut a tile
# along its sub-tile diagonal, scaled down from the cells'
_TRIANGLE_CALLS = {
    "causal-s1024-tile512": (2, 2, True, 512, 1024, 1024, 2, 2),
    "causal-s2048-tile512": (1, 1, True, 512, 2048, 2048, 4, 4),
    "causal-s768-tile384-three-sub-tiles": (2, 1, True, 384, 768, 768, 2, 2),
    "gqa-8-to-2": (8, 2, True, 256, 512, 512, 2, 2),
    # keys wider than values: 128 + 64 channels, ONE rotary key head
    "in-parts-128-and-64": (2, 2, True, 256, 512, 512, 2, 2, 64),
    # both kinds of its triangles: x_0's diagonal tiles (k_blk <= q_blk) and
    # the x_t -> x_0 ones (k_blk < q_blk), beside two DIAGONAL steps
    "block-diffusion-both-kinds": (4, 1, BlockDiffusion(512, 4), 256, 1024,
                                   1024, 4, 4),
    # more keys than queries: a tile, a sub-tile or half a sub-tile apart the
    # diagonal leaves the tiles it crosses one side of their sub-tile
    # diagonal empty
    "keys-512-ahead": (2, 1, True, 256, 512, 1024, 2, 2),
    "keys-128-ahead": (2, 1, True, 256, 512, 640, 2, 2),
    "keys-64-ahead": (2, 1, True, 256, 512, 576, 2, 2),
    # the last block padded, queries and keys
    "padded-last-block": (2, 1, True, 256, 900, 900, 4, 4),
    # a window of two tiles: a row's trailing tile (a strict upper triangle)
    # and its own, triangles of either hand in every kernel
    "window-two-tiles-both-hands": (2, 1, SlidingWindow(512), 256, 1024,
                                    1024, 6, 6),
}


def _against_the_dense_mask(heads, kv_heads, rule, tile, s_q, s_k, rope=0):
    """Forward and the gradients of every operand, the Pallas kernels in
    interpret mode against `_reference_attention`, which builds the DENSE
    mask; `rope`: the rotary channels of a call in parts."""
    d = 128 if rope else 32
    q, k, v = _make_qkv(S=s_k, H=heads, kv_heads=kv_heads, D=d, seed=s_q)
    parts = ()
    if rope:
        keys = jax.random.split(jax.random.PRNGKey(11), 2)
        parts = (jax.random.normal(keys[0], (1, s_k, heads, rope)),
                 jax.random.normal(keys[1], (1, s_k, 1, rope)))

    def loss(q, k, v, *parts, **how):
        rotary = dict(zip(("q_rope", "k_rope"), parts))
        if rotary:
            rotary["q_rope"] = rotary["q_rope"][:, :s_q]
        out = flash_attention(q[:, :s_q], k, v, causal=rule, **rotary, **how)
        return jnp.sum(out ** 2), out

    wrt = tuple(range(3 + len(parts)))
    (_, out), g1 = jax.value_and_grad(loss, argnums=wrt, has_aux=True)(
        q, k, v, *parts, interpret=True, block_q=tile, block_k=tile)
    (_, ref), g2 = jax.value_and_grad(loss, argnums=wrt, has_aux=True)(
        q, k, v, *parts, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("case", sorted(_TRIANGLE_CALLS))
def test_flash_attention_runs_a_cut_tile_on_its_kept_sub_tiles(case):
    """Forward and the three gradients of the Pallas kernels (interpret
    mode) where the plans hold `Triangle` steps, against
    `_reference_attention`, which builds the DENSE mask."""
    heads, kv_heads, rule, tile, s_q, s_k, fwd, dkv, *rope = \
        _TRIANGLE_CALLS[case]
    plans = block_schedule(s_q, s_k, _clamp_block(tile, s_q),
                           _clamp_block(tile, s_k), rule)
    assert [plans[name].steps_triangle for name in ("fwd", "dq", "dkv")] \
        == [fwd, fwd, dkv]
    assert all(plan.static for plan in plans.values())
    _against_the_dense_mask(heads, kv_heads, rule, tile, s_q, s_k, *rope)


# (q heads, kv heads, rule, tile, s_q, s_k, the steps of the forward's plan and
# of dk/dv's that run in a loop's bodies[, rotary channels]): loop plans whose
# rows run their whole tiles several a body, `_LOOP_BODY`'s 4 and then 2,
# with no mask, and the rest one an iteration under it
_LOOP_BODY_CALLS = {
    # rows of 0 to 9 whole tiles: shorter than a body (0, 1), one body of 2
    # (2, 3), one of 4 (4, 5), one of each (6, 7), two of 4 (8, 9)
    "causal-rows-of-1-to-10": (2, 2, True, 128, 1280, 1280, 40, 40),
    "gqa-4-to-1": (4, 1, True, 128, 1280, 1280, 40, 40),
    # more keys than queries: the shortest row holds 6 whole tiles
    "keys-768-ahead": (2, 1, True, 128, 640, 1408, 38, 32),
    # the last block padded, queries and keys: dk/dv's last query tile is
    # under the mask in every row
    "padded-last-block": (2, 1, True, 128, 1200, 1200, 40, 32),
    # keys wider than values: 128 + 64 channels, ONE rotary key head
    "in-parts-128-and-64": (2, 2, True, 128, 1280, 1280, 40, 40, 64),
    # no rule at all, the last block padded: every tile but a row's last is
    # whole
    "no-rule-padded": (2, 1, False, 128, 1216, 1216, 80, 80),
    # a block-diffusion row's whole tiles are x_0's before its block
    "block-diffusion": (2, 1, BlockDiffusion(1280, 4), 128, 2560, 2560,
                        80, 90),
    # a window's edge rows loop beside the rows that share a branch
    "window-edge-rows-beside-shared-rows": (
        2, 1, SlidingWindow(1024), 128, 2560, 2560, 24, 24),
}


@pytest.mark.parametrize("case", sorted(_LOOP_BODY_CALLS))
def test_flash_attention_where_a_loops_body_runs_several_steps(case):
    """Forward and the gradients of every operand of the Pallas kernels
    (interpret mode) where a loop plan's rows run steps in bodies, against
    `_reference_attention`, which builds the DENSE mask."""
    heads, kv_heads, rule, tile, s_q, s_k, fwd, dkv, *rope = \
        _LOOP_BODY_CALLS[case]
    plans = block_schedule(s_q, s_k, tile, tile, rule)
    assert [(plans[name].static, plans[name].steps_loop_body)
            for name in ("fwd", "dq", "dkv")] \
        == [(False, fwd), (False, fwd), (False, dkv)]
    assert all(plan.body == _LOOP_BODY["fwd"] == (4, 2)
               for plan in plans.values())
    assert (plans["fwd"].shared is not None) == isinstance(rule,
                                                           SlidingWindow)
    _against_the_dense_mask(heads, kv_heads, rule, tile, s_q, s_k, *rope)


def test_a_window_of_several_tiles_at_the_cell_shape():
    """train-smallthinker-1chip's two calls, S 16,384 in tiles of 512. The
    window layers (4,096 keys = 8 tiles): a row walks the window's trailing
    tile (a strict upper triangle), 7 whole tiles and its own diagonal one,
    9 steps, 252 a (batch, head) where `CAUSAL` walks 528 in rows of up to
    32; a run of 9 tiles is no band (a band is at most two steps long), and
    both are past every unroll budget: loops. But 24 of the window's 32
    rows are ONE row, placed by the grid row (the forward's and dq's 8-31,
    dk/dv's 0-23): they share one unrolled branch, the 7 steps with no mask
    first and then the two cut tiles, whole under the mask, and the 8 edge
    rows keep the loop. No two causal rows are alike: its rows all loop. A
    row of the loop runs its whole tiles in bodies of 4 and then of 2 steps
    with no mask (PR 60: 480 of the causal plan's 528 steps, 24 of the edge
    rows' 36) and what is left, its diagonal tile among it, one step an
    iteration under the mask. What runs over what the rules keep is the
    loops': 1.125 and 1.031."""
    window = block_schedule(16384, 16384, 512, 512, SlidingWindow(4096))
    causal = block_schedule(16384, 16384, 512, 512, True)
    assert SlidingWindow(4096).needed(16384, 16384) == 58_722_304
    assert CAUSAL.needed(16384, 16384) == 134_225_920
    for plans, steps, longest, over in ((window, 252, 9, 1.125),
                                        (causal, 528, 32, 1.031)):
        assert plans["dq"] is plans["fwd"]
        for plan in plans.values():
            assert not plan.static
            assert plan.steps_band == 0 == plan.steps_triangle
            assert (len(plan.tiles), max(map(len, plan.rows))) \
                == (steps, longest)
            assert plan.executed_over_needed == pytest.approx(over, abs=1e-3)
    for name, plan in causal.items():
        assert plan.shared is None and plan.steps_shared == 0
        assert (plan.steps_unmasked, plan.steps_masked) == (480, 48)
        assert plan.steps_loop_body == 480 and plan.body == (4, 2)
        # a row of n whole tiles (the forward's row i: i; dk/dv's: 31 - i):
        # (n // 4, n % 4 // 2) bodies, then its diagonal tile and the odd
        # whole one under the mask; in the table after the three counts,
        # the whole tiles first
        assert plan.bodies == tuple((n // 4, n % 4 // 2) for n in (
            range(31, -1, -1) if name == "dkv" else range(32)))
        assert plan.table.shape == (32, 3 + 32)
        longest = 0 if name == "dkv" else 31
        assert plan.table[longest].tolist() == [2, 7, 1] + (
            list(range(1, 31)) + [0, 31] if name == "dkv" else list(range(32)))
    for name, plan in window.items():
        # from the row's own block: the forward's and dq's rows walk back
        # over the keys, dk/dv's on over the queries
        first, cut = (0, (0, 8)) if name == "dkv" else (8, (-8, 0))
        assert plan.shared == SharedRows(
            tuple(range(first, first + 24)), 1,
            tuple((at, False) for at in range(cut[0] + 1, cut[1]))
            + tuple((at, True) for at in cut))
        assert plan.steps_shared == 216
        for i, row in enumerate(plan.rows):
            if i in plan.shared.rows:   # the branch, placed by the row
                assert row == tuple(sorted(
                    (i + at, m) for at, m in plan.shared.steps))
            else:   # the loop's: whole tiles in bodies, the rest masked
                assert 1 <= len(row) <= 8
                lead = 4 * plan.bodies[i][0] + 2 * plan.bodies[i][1]
                assert [m for _, m in row] \
                    == [False] * lead + [True] * (len(row) - lead)
        assert (plan.steps_unmasked, plan.steps_masked) == (192, 60)
        assert plan.steps_loop_body == 24
        assert plan.table.shape == (32, 12)   # the edge rows read it still
    # asked tile by tile, 7 of a row's 9 need no mask
    rule = SlidingWindow(4096)
    assert [rule.tile(20 * 512, 512, k0 * 512, 512) for k0 in range(11, 22)] \
        == [(False, False), (True, False)] + [(True, True)] * 7 \
        + [(True, False), (False, False)]


# the plans of the other cells' long calls, at 512 x 512: (queries, keys, rule,
# the digests of the forward's / dq's plan and of dk/dv's (the loops' since PR
# 60, whose bodies run whole tiles with no mask; Laguna's window plan, which
# is unrolled, PR 57's still), the steps in a loop's bodies of all a kernel's
# steps). No two causal rows are alike; EVA's largest group (16 rows of 4
# steps) holds 64 of the forward's 304 steps
_PLANS_WITH_NO_SHARED_ROWS = {
    "causal-s8192": (8192, 8192, True,
                     "6a5cedcafb14667f", "337e6d5831d1eced", (112, 136)),
    "causal-s16384": (16384, 16384, True,
                      "fd0e0a92e41c9a71", "3aea0742811aea95", (480, 528)),
    "causal-s32768": (32768, 32768, True,
                      "d3535a2460e63cb0", "ff525787c888fe4a", (1984, 2080)),
    "window-512-s8192": (8192, 8192, SlidingWindow(512),
                         "93608cf8e569bd02", "bc8b28505b3b45cb", (0, 16)),
    "eva-s32768": (32768, 34816, EvaWindows(32768, 2048, 16),
                   "69514c7086e30892", "a79726d245f489da", (160, 304)),
}


@pytest.mark.parametrize("case", sorted(_PLANS_WITH_NO_SHARED_ROWS))
def test_rows_share_a_branch_only_where_most_of_a_loop_plan_is_one_row(case):
    """The other cells' calls plan what they planned, field by field, and no
    row of theirs shares a branch: what decides is read off the plan (several
    rows of one shape that hold half its steps), not off the rule's type."""
    s_q, s_k, rule, fwd, dkv, (in_bodies, steps) = \
        _PLANS_WITH_NO_SHARED_ROWS[case]
    plans = block_schedule(s_q, s_k, 512, 512, rule)
    assert plans["dq"] is plans["fwd"]
    for plan, digest in ((plans["fwd"], fwd), (plans["dkv"], dkv)):
        assert plan.shared is None and plan.steps_shared == 0
        assert _plan_digest(plan) == digest
        # the four cells' loop calls (PR 60): the whole tiles of each row
        # in bodies of 4 and of 2 steps with no mask, in every kernel
        assert (plan.steps_loop_body, len(plan.tiles)) == (in_bodies, steps)
        assert plan.steps_unmasked == in_bodies
        assert plan.body == (() if plan.static else _LOOP_BODY["fwd"])


# (q heads, kv heads, window, tile or (block_q, block_k), S, the rows that
# share a branch in the forward's plan and in dk/dv's, the steps of the branch
# with no mask and those under it, the forward's and dk/dv's): loop plans
# whose same-shaped rows run ONE unrolled branch, the cell's 28 / 4 x window
# 4,096 in tiles of 512 scaled down: whole tiles with no mask, then the two
# the rule cuts; a last block that is padded (its own tile's mask holds the
# padding too, so it is the others' shape); one sub-tile a tile; a key block
# of TWO steps of the queries' (`stride` 2: four of its six tiles are cut)
_SHARED_ROW_CALLS = {
    "2-to-1-window-spans-8-tiles": (
        4, 2, 2048, 256, 4096, range(8, 16), range(0, 8), (7, 2), (7, 2)),
    "2-to-1-padded-last-block": (
        2, 1, 1024, 256, 3000, range(4, 12), range(0, 8), (3, 2), (3, 2)),
    "7-to-1-tiles-of-one-sub-tile": (
        7, 1, 1024, 128, 2560, range(8, 20), range(0, 12), (7, 2), (7, 2)),
    "2-to-1-key-blocks-of-two-steps": (
        2, 1, 512, (128, 256), 2048, range(4, 16), range(0, 6), (3, 2),
        (2, 4)),
}


@pytest.mark.parametrize("case", sorted(_SHARED_ROW_CALLS))
def test_flash_attention_where_rows_of_one_shape_share_a_branch(case):
    """Forward and the three gradients of the Pallas kernels (interpret
    mode) where a loop plan's majority of rows run one shared branch and the
    others the loop, against `_reference_attention`, which builds the DENSE
    mask."""
    heads, kv_heads, window, tile, s, fwd_rows, dkv_rows, fwd, dkv = \
        _SHARED_ROW_CALLS[case]
    block_q, block_k = tile if isinstance(tile, tuple) else (tile, tile)
    rule = SlidingWindow(window)
    plans = block_schedule(s, s, block_q, block_k, rule)
    for name, rows, kinds in (("fwd", fwd_rows, fwd), ("dq", fwd_rows, fwd),
                              ("dkv", dkv_rows, dkv)):
        plan = plans[name]
        assert not plan.static
        assert plan.shared.rows == tuple(rows)
        assert plan.shared.stride == (block_k // block_q if name == "dkv"
                                      else 1)
        assert [m for _, m in plan.shared.steps] \
            == [False] * kinds[0] + [True] * kinds[1]
        assert plan.steps_shared == len(rows) * sum(kinds)
    q, k, v = _make_qkv(S=s, H=heads, kv_heads=kv_heads, D=32, seed=window)

    def loss(q, k, v, **how):
        out = flash_attention(q, k, v, mask=rule, **how)
        return jnp.sum(out ** 2), out

    (_, out), g1 = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v, interpret=True, block_q=block_q, block_k=block_k)
    (_, ref), g2 = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
        q, k, v, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_runs_that_step_by_one_difference():
    """What a shared branch traces once and unrolls: its steps of one kind,
    and the test for its rows, in runs that each step by one difference."""
    from ray_tpu.ops.flash_attention import _among, _progressions

    assert _progressions(range(-7, 0)) == [[-7, 7, 1]]
    assert _progressions([-8, 0]) == [[-8, 2, 8]]
    assert _progressions([0, 1, 2, 5, 8, 9]) == [[0, 3, 1], [5, 2, 3],
                                                 [9, 1, 1]]
    assert _progressions([3]) == [[3, 1, 1]] and _progressions([]) == []
    for rows in (range(8, 32), (0, 1, 2, 5, 8, 9), (3,), (1, 4, 7, 8)):
        assert [bool(_among(np.int32(i), tuple(rows))) for i in range(34)] \
            == [i in rows for i in range(34)]


def test_flash_attention_counts_the_steps_of_a_shared_branch():
    """`flash.steps_shared_row`: the steps, a (batch, head), of the rows that
    run a shared branch, a lowering of each kernel as its siblings are; a
    plan with no such rows adds nothing. `flash.steps_loop_body`: those of
    the loop's rows that run in its bodies of several steps."""
    from ray_tpu._private import device_profiler

    def counted(rule, s):
        q, k, v = (jax.ShapeDtypeStruct((1, s, h, 32), jnp.float32)
                   for h in (2, 1, 1))
        before = device_profiler.snapshot()["counters"]
        jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=rule, use_pallas=True, block_q=256,
            block_k=256).sum()))(q, k, v)
        after = device_profiler.snapshot()["counters"]
        return {name: after[name] - before.get(name, 0) for name in (
            "flash.steps_shared_row", "flash.steps_loop_body",
            "flash.steps_unmasked", "flash.steps_masked",
            "flash.steps_triangle")}

    # 12 rows, 8 of them the one row of 5 steps, in each of three kernels: 3
    # whole tiles with no mask and the two the rule cuts; rows of 1, 2, 3, 4
    # loop, the last two with a body of 2 whole tiles
    assert counted(SlidingWindow(1024), 3072) == {
        "flash.steps_shared_row": 3 * 8 * 5, "flash.steps_loop_body": 3 * 4,
        "flash.steps_unmasked": 3 * (8 * 3 + 4),
        "flash.steps_masked": 3 * (8 * 2 + 6), "flash.steps_triangle": 0}
    # rows of 1 to 12 steps, 0 to 11 of them whole tiles: 2 x (0 + 0 + 1 +
    # 1) + 6 x 4 + 2 x 8 of them in bodies of 4, then 6 rows a body of 2
    assert counted(True, 3072) == {
        "flash.steps_shared_row": 0, "flash.steps_loop_body": 3 * 60,
        "flash.steps_unmasked": 3 * 60, "flash.steps_masked": 3 * 18,
        "flash.steps_triangle": 0}


def test_a_kernel_states_a_vmem_limit_only_past_the_default():
    """`_vmem_limit`: the blocks twice plus 4 MiB against the v5e's 16 MiB.
    [8192, 128] bf16 K and V (every cell's longest call before PR 50) are
    8.5 MiB of blocks twice over: no limit, the call lowers as it did;
    [16384, 128] are 16.5 MiB: blocks + 16 MiB. Counted a lowering."""
    from jax.experimental import pallas as pl

    from ray_tpu._private import device_profiler
    from ray_tpu.ops.flash_attention import _vmem_limit

    def limit(s, dtype=jnp.bfloat16):
        whole = pl.BlockSpec((1, 1, s, 128), lambda *i: (0, 0, 0, 0))
        tile = pl.BlockSpec((1, 1, 512, 128), lambda *i: (0, 0, 0, 0))
        x = jax.ShapeDtypeStruct((1, 4, s, 128), dtype)
        return _vmem_limit(([tile, (whole,), whole], tile), ([x, (x,), x], x))

    assert limit(2048) is None and limit(8192) is None
    assert limit(16384) == 2 * (2 * 16384 + 2 * 512) * 128 * 2 + 16 * 2**20
    assert limit(8192, jnp.float32) is not None
    q, k, v = (jax.ShapeDtypeStruct((1, 256, h, 32), jnp.float32)
               for h in (7, 1, 1))
    before = device_profiler.snapshot()["counters"]
    jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, use_pallas=True, block_q=128, block_k=128).sum()))(q, k, v)
    after = device_profiler.snapshot()["counters"]
    assert after["flash.kernels"] - before.get("flash.kernels", 0) == 3
    assert after["flash.kernels_vmem_stated"] \
        == before.get("flash.kernels_vmem_stated", 0)


def test_block_diffusion_rule_wants_whole_blocks_over_both_halves():
    with pytest.raises(ValueError):
        block_schedule(512, 512, 128, 128, BlockDiffusion(200, 4))
    with pytest.raises(ValueError):
        block_schedule(400, 400, 128, 128, BlockDiffusion(200, 3))


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of the jaxprs in its parameters
    (a jit, a custom_vjp, a shard_map) too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(inner)


def _operand_shapes(fn, *args, primitive="pallas_call"):
    return [[v.aval.shape for v in eqn.invars]
            for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == primitive]


@pytest.mark.parametrize("rule", [True, BlockDiffusion(128, 4),
                                  SlidingWindow(128)],
                         ids=["causal", "block-diffusion", "window"])
def test_gqa_call_hands_the_kernels_k_and_v_at_the_kv_heads_count(rule):
    """On the kernel path no K or V exists at the query heads' count: the
    three `pallas_call`s take K and V [B, H_kv, S, D] as they are (dk and dv
    leave dk/dv one a QUERY head, the shape the roofline readers take, and
    the rule sums them), and nothing K- or V-shaped with H heads is in the
    forward's jaxpr."""
    b, s_q, s_k, h, h_kv, d = 2, 128, 256, 8, 2, 32
    if isinstance(rule, BlockDiffusion):
        s_q = s_k
    q = jax.ShapeDtypeStruct((b, s_q, h, d), jnp.float32)
    k = v = jax.ShapeDtypeStruct((b, s_k, h_kv, d), jnp.float32)

    def call(q, k, v):
        return flash_attention(q, k, v, causal=rule, use_pallas=True,
                               block_q=128, block_k=128)

    if s_q != s_k:  # then whatever is K-shaped with H heads is a copy
        forward = str(jax.make_jaxpr(call)(q, k, v))
        assert f"f32[{b},{h_kv},{s_k},{d}]" in forward
        assert f"f32[{b},{h},{s_k},{d}]" not in forward
        assert f"f32[{b},{s_k},{h},{d}]" not in forward
    kernels = _operand_shapes(
        jax.grad(lambda q, k, v: call(q, k, v).sum(), argnums=(0, 1, 2)),
        q, k, v)
    assert len(kernels) == 3  # forward, dq, dk/dv
    for operands in kernels:
        operands = [x for x in operands if len(x) == 4]  # not a loop's table
        assert operands[0] == (b, h, s_q, d)
        assert operands[1] == operands[2] == (b, h_kv, s_k, d)


# sha256 of the call as traced (value and gradients, the three kernels'
# bodies in it), a call whose K has the grid's heads and a call in parts,
# whose one rotary key was always read at head 0. In tiles of 128, one
# sub-tile, no plan has a triangle step, and the calls trace to the text of
# the commit before a KV head was shared (PR 48's), which is PR 50's too;
# in tiles of 256 each call's diagonal tiles are triangle steps since PR 51
# (3 of their 4 sub-tiles) and the text is that PR's, pinned anew. ALL EIGHT
# pinned anew at PR 63: the backward pass hands lse and delta to its kernels
# lane-dense (`_stat_forms`), the forward's equations as they were
_TRACED = {
    ("mha-causal", 128):
        "e360dceab57a55eafba1ac642c601b55e2fea4690f6fb405d011f09c1edf7210",
    ("mha-window-band", 128):
        "ecc34b4a86df86d8ba421fc68e001d4d58e43747f809860ab04d3a28b7fd576f",
    ("mha-block-diffusion", 128):
        "317b456e428b8f99833d82e9eb4ef46fd93cafc89759c6175a031439cf1742a0",
    ("in-parts", 128):
        "921bf9ac10baf5c4be97f024cdd2dcf580144cdc456ddf342c0a64a0ca119afd",
    ("mha-causal", 256):
        "4a011bf5806136331aa6d3fc96f0cd7c9f1b557afcdd1b3e8d593dc01f01268f",
    ("mha-window-band", 256):
        "84d86702905ad8ed451fd475b9b64ca1520f3aedbd5be146eba313c166591882",
    ("mha-block-diffusion", 256):
        "cd6285ea55d2aefea071bfbf308490a4e979ae4f259adda10814729ed63693c5",
    ("in-parts", 256):
        "740f813210e915be1e38cde78c12a9464ac90f7a449f96af88f9672709834884",
}


@pytest.mark.parametrize("case, tile", sorted(_TRACED),
                         ids=["-".join(map(str, key)) for key in
                              sorted(_TRACED)])
def test_calls_with_no_shared_kv_head_trace_to_what_they_were(case, tile):
    rule = {"mha-window-band": SlidingWindow(256),
            "mha-block-diffusion": BlockDiffusion(256, 4)}.get(case, True)
    plans = block_schedule(512, 512, tile, tile, rule)
    assert all(bool(plan.steps_triangle) == (tile == 256)
               for plan in plans.values())
    shapes = [(2, 512, 4, 64)] * 3
    if case == "in-parts":
        shapes += [(2, 512, 4, 32), (2, 512, 1, 32)]

    def call(q, k, v, *rope):
        parts = dict(zip(("q_rope", "k_rope"), rope))
        return flash_attention(q, k, v, causal=rule, use_pallas=True,
                               block_q=tile, block_k=tile, **parts) \
            .astype(jnp.float32).sum()

    traced = jax.make_jaxpr(jax.value_and_grad(
        call, argnums=tuple(range(len(shapes)))))(
            *[jax.ShapeDtypeStruct(x, jnp.bfloat16) for x in shapes])
    assert hashlib.sha256(re.sub(r" at 0x[0-9a-f]+", "", str(traced))
                          .encode()).hexdigest() == _TRACED[case, tile]


@pytest.mark.parametrize("kv_heads", [2, 1],
                         ids=["tp-divides-kv-heads", "fewer-kv-heads-than-tp"])
@pytest.mark.parametrize("interpret", [False, True], ids=["oracle", "kernels"])
def test_flash_attention_rule_on_a_mesh(interpret, kv_heads):
    """`flash_attention_sharded` carries the rule through its shard_map
    (batch over fsdp, heads over tp; a rule speaks of positions only, so
    every shard runs under it as it is): values and gradients equal the
    unsharded call's. The KV heads ride tp as they are where tp divides
    them (a shard's 4 query heads over its ONE of 2 KV heads); ONE KV head
    under tp 2 is repeated to 2, a head a shard, never to the 8 of q."""
    from ray_tpu.ops.flash_attention import flash_attention_sharded
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    mesh = build_mesh(MeshConfig(dp=1, fsdp=2, tp=2),
                      devices=jax.devices()[:4])
    rule = BlockDiffusion(128, 4)
    q, k, v = _make_qkv(B=2, S=256, H=8, kv_heads=kv_heads, D=32, seed=9)
    do = jax.random.normal(jax.random.PRNGKey(5), q.shape)

    def sharded(q, k, v):
        return flash_attention_sharded(
            q, k, v, mesh, mask=rule, interpret=interpret, block_q=128,
            block_k=128)

    (shards,) = _operand_shapes(sharded, q, k, v, primitive="shard_map")
    assert shards == [(2, 256, 8, 32)] + [(2, 256, 2, 32)] * 2
    want, vjp_want = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, mask=rule), q, k, v)
    got, vjp = jax.vjp(jax.jit(sharded), q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    for a, w in zip(vjp(do), vjp_want(do)):
        assert a.shape == w.shape
        np.testing.assert_allclose(a, w, rtol=2e-3, atol=2e-4)


# --------------------------------------------------------------------------
# the experts' forms over the grouped matmuls, under a routing handed in
# --------------------------------------------------------------------------

@pytest.mark.parametrize("held", [None, (4, 8)], ids=["whole", "share"])
def test_reglu_experts_under_a_routing_handed_in_match_their_formula(held):
    """`moe_layer(form="reglu", routing=)`: the choice formed EARLIER by
    `route` from another tensor than the rows dispatched (a router ahead of
    its attention), the experts down(relu(gate u) * up u): value and
    `jax.vjp` against every held expert applied to every token under the
    choice's weights; whole, the weights' gradient reaches what the ROUTER
    read; on a share they are constants."""
    from ray_tpu._private import device_profiler
    from ray_tpu.parallel import moe

    t, d, f, e, k = 48, 32, 24, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    read = jax.random.normal(ks[0], (t, d))     # what the router reads
    u = jax.random.normal(ks[1], (t, d))        # what is dispatched
    router = jax.random.normal(ks[2], (d, e)) * 0.5
    first, n_held = held or (0, e)
    mine = {"w_gate": jax.random.normal(ks[3], (n_held, d, f)) * d ** -0.5,
            "w_up": jax.random.normal(ks[4], (n_held, d, f)) * d ** -0.5,
            "w_down": jax.random.normal(ks[5], (n_held, f, d)) * f ** -0.5}

    def formula(read, u, mine):
        logits = read @ router
        _, chosen = jax.lax.top_k(logits, k)
        chose = jax.nn.one_hot(chosen, e)
        w = jax.nn.softmax(jnp.sum(chose * logits[:, None], -1), -1)
        w = jnp.sum(chose * w[..., None], 1)
        if held:
            w = jax.lax.stop_gradient(w)
        return sum(w[:, first + i:first + i + 1] * (
            (jax.nn.relu(u @ mine["w_gate"][i]) * (u @ mine["w_up"][i]))
            @ mine["w_down"][i]) for i in range(n_held))

    def layer(read, u, mine):
        routing = moe.route(read, router, k, True, score="softmax")
        return moe.moe_layer(u, None, mine, k, held=held, form="reglu",
                             routing=routing)[0]

    before = device_profiler.snapshot()["counters"]
    with jax.default_matmul_precision("highest"):
        got, vjp = jax.vjp(layer, read, u, mine)
        want, vjp_want = jax.vjp(formula, read, u, mine)
    after = device_profiler.snapshot()["counters"]
    assert after.get("moe.latent_rows", 0) == before.get("moe.latent_rows", 0)
    assert after["moe.gmm_calls"] - before.get("moe.gmm_calls", 0) == 3
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    g = jax.random.normal(jax.random.PRNGKey(12), got.shape)
    grads, grads_want = vjp(g), vjp_want(g)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=2e-5)
    assert bool(jnp.any(grads[0] != 0)) == (held is None)
    with pytest.raises(ValueError, match="routing"):
        moe.moe_layer(u[:-1], None, mine, k, held=held, form="reglu",
                      routing=moe.route(read, router, k, True))


# --------------------------------------------------------------------------
# PR 55: the scan at ONE wide group and a chunk of the caller's, the flash
# call at a 64-wide head with a scale of its own, and the pins that the
# calls of the nine cells there were trace to what they did
# --------------------------------------------------------------------------

import functools  # noqa: E402

from ray_tpu._private import device_profiler  # noqa: E402
from ray_tpu.ops import ssd as ssd_op  # noqa: E402

_SSD_TENSORS = ("y", "state", "dx", "ddelta", "da", "db", "dc")
# (heads, head dim, chunk, S, how): ONE group throughout. `jnp` at a chunk
# of 128 and 256 with S no multiple of either; the kernels interpreted at a
# group of 2,048 lanes, past `_BLOCK_LANES`, which they walk in two blocks
# of 16 heads (the Granite cell's block), and at a narrow one under a chunk
# of the caller's
_SSD_CASES = {
    "jnp-chunk128": (8, 16, 128, 300, "jnp"),
    "jnp-chunk256": (8, 16, 256, 300, "jnp"),
    "kernels-wide-group-chunk256": (32, 64, 256, 300, "kernel"),
    "kernels-wide-group-chunk128": (32, 64, 128, 200, "kernel"),
    "kernels-narrow-group-chunk64": (8, 16, 64, 150, "kernel"),
}


@functools.lru_cache(maxsize=None)
def _ssd_one_group(case):
    """-> ({tensor: got}, {tensor: by the recurrence}): y, the final state
    and the five gradients of sum(y * w) + sum(state)."""
    h, p, chunk, s, how = _SSD_CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(55), 6)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (1, s, h)) - 2.0)
    a = -jnp.exp(jax.random.normal(ks[2], (h,))) * delta
    args = (jax.random.normal(ks[0], (1, s, h, p)), delta, a,
            jax.random.normal(ks[3], (1, s, 1, 8)),
            jax.random.normal(ks[4], (1, s, 1, 8)))
    w = jax.random.normal(ks[5], (1, s, h, p))

    def everything(fn):
        def scalar(*a):
            y, state = fn(*a)
            return jnp.sum(y * w) + jnp.sum(state), (y, state)
        grads, out = jax.grad(scalar, argnums=(0, 1, 2, 3, 4),
                              has_aux=True)(*args)
        return dict(zip(_SSD_TENSORS, out + grads))

    with jax.default_matmul_precision("highest"):
        return (everything(lambda *a: ssd_op.ssd_scan(
            *a, chunk=chunk, use_pallas=False, interpret=how == "kernel")),
            everything(ssd_op.ssd_recurrence))


@pytest.mark.parametrize("tensor", _SSD_TENSORS)
@pytest.mark.parametrize("case", list(_SSD_CASES))
def test_ssd_at_one_group_and_the_callers_chunk_matches_the_recurrence(
        case, tensor):
    got, want = _ssd_one_group(case)
    assert bool(jnp.all(jnp.isfinite(got[tensor])))
    scale = float(jnp.abs(want[tensor]).max())
    np.testing.assert_allclose(got[tensor] / scale, want[tensor] / scale,
                               atol=2e-5)


def test_ssd_walks_a_wide_group_in_head_blocks_and_counts_it():
    """64 heads of 64 (4,096 lanes) are four blocks of 16, Nemotron's 16 of
    64 one; the kernels of a wide group are counted as such, at the call's
    own chunk."""
    assert ssd_op._head_blocks(64, 64) == 4
    assert ssd_op._head_blocks(16, 64) == 1
    assert ssd_op._head_blocks(24, 64) == 2     # 12 + 12: equal blocks
    assert ssd_op._head_blocks(3, 1024) == 3
    with pytest.raises(ValueError, match="past"):
        ssd_op._head_blocks(2, 2048)
    f32 = jnp.float32
    shaped = jax.ShapeDtypeStruct

    def lowered(h, g, s, chunk):
        before = device_profiler.snapshot()["counters"]
        args = (shaped((1, s, h, 64), f32), shaped((1, s, h), f32),
                shaped((1, s, h), f32), shaped((1, s, g, 8), f32),
                shaped((1, s, g, 8), f32))
        jax.make_jaxpr(jax.grad(lambda *a: ssd_op.ssd_scan(
            *a, chunk=chunk, interpret=True)[0].sum()))(*args)
        after = device_profiler.snapshot()["counters"]
        return {k: after.get(k, 0) - before.get(k, 0) for k in (
            "ssd.kernels", "ssd.kernels_wide_group", "ssd.chunks")}

    assert lowered(32, 1, 520, 256) == {
        "ssd.kernels": 3, "ssd.kernels_wide_group": 3, "ssd.chunks": 3}
    assert lowered(32, 2, 520, 128) == {
        "ssd.kernels": 3, "ssd.kernels_wide_group": 0, "ssd.chunks": 5}


# sha256 of `ssd_scan`'s value and gradients as traced at the Nemotron
# cell's shape, x [2, 2048, 128, 64] in 8 groups of 16 heads, chunk 128,
# the three kernels' bodies and their index maps in it: PR 53's text
_SSD_NEMOTRON = \
    "e4ffb51d722de1bf0346e42c4604a1fb232de66ff1bdf14a0884a0b1a19004f7"


def _traced_digest(fn, shapes):
    traced = jax.make_jaxpr(fn)(*shapes)
    return hashlib.sha256(re.sub(r" at 0x[0-9a-f]+", "", str(traced))
                          .encode()).hexdigest()


def test_the_nemotron_cells_scan_traces_to_what_it_was():
    bf16, f32 = jnp.bfloat16, jnp.float32
    shaped = jax.ShapeDtypeStruct
    shapes = (shaped((2, 2048, 128, 64), bf16), shaped((2, 2048, 128), f32),
              shaped((2, 2048, 128), f32), shaped((2, 2048, 8, 128), bf16),
              shaped((2, 2048, 8, 128), bf16))
    assert _traced_digest(jax.value_and_grad(
        lambda *a: ssd_op.ssd_scan(*a, use_pallas=True)[0].astype(f32).sum(),
        argnums=(0, 1, 2, 3, 4)), shapes) == _SSD_NEMOTRON


# sha256 of the flash call, value and gradients as traced, at each of the
# nine cells' shapes before PR 55: (batch, S, heads, KV heads, head dim, the
# rule, the rotary part's width of a call in parts); train-4chip's is a tp 2
# shard's. PR 63's text, all twelve: the backward pass's row statistics reach
# dq and dk/dv lane-dense and delta is a reduce with no `keepdims`; the
# FORWARD's equations in each are PR 62's (`_CELL_FORWARDS` below).
_CELL_CALLS = {
    "train-1chip": (
        (4, 2048, 32, 8, 128, True, 0),
        "3d03f2ffea8aa4a49f4bf6a970fabc44715e18365327564ee7b25a3311e3c276"),
    "train-4chip": (
        (2, 2048, 16, 4, 128, True, 0),
        "343d805110c5b6582276d1da431f861a2daf3b6f560c31ea50607208221e5682"),
    "train-olmoe-1chip": (
        (4, 2048, 16, 16, 128, True, 0),
        "ad76d56770413ba46a3f2695a9013db5eb980b305d753f9d69a3683cd93d10fe"),
    "train-joyai-1chip": (
        (4, 2048, 32, 32, 128, True, 64),
        "bcab5e430ccf8d0d1743f5f57618ad8b1f42e8ee0ead99eefa20f012d89480fe"),
    "train-sdar-1chip": (
        (4, 4096, 32, 4, 128, BlockDiffusion(2048, 4), 0),
        "29175e0770f686fabf18e3370c029a7689573e9ed865ec1c4944809863083297"),
    "train-ling-1chip": (
        (4, 2048, 32, 32, 128, True, 64),
        "bcab5e430ccf8d0d1743f5f57618ad8b1f42e8ee0ead99eefa20f012d89480fe"),
    "train-nemotron3-1chip": (
        (2, 2048, 32, 2, 128, True, 0),
        "05d5aededff4b8dd102abc3635193b684ad0b6071ea92dca50ad714b1c9e583a"),
    "train-laguna-1chip.window": (
        (1, 8192, 64, 8, 128, SlidingWindow(512), 0),
        "b14a39736bb7c91ba56c9d41d1a071c6c84ced79b7e11d4c43483ce1968a2945"),
    # a loop plan's rows run their whole tiles in bodies of 4 and of 2 steps
    # with no mask (PR 60), as the three loop calls below
    "train-laguna-1chip.full": (
        (1, 8192, 48, 8, 128, True, 0),
        "37bab37bd83184053ea900bd6c0cda8b644ba109023b981288887b66f191ec66"),
    # 24 of its 32 grid rows share ONE unrolled branch (PR 58); the 8 edge
    # rows' loop has the bodies
    "train-smallthinker-1chip.window": (
        (1, 16384, 28, 4, 128, SlidingWindow(4096), 0),
        "110f183065c6399fb21708c40586059a82a8610e00d6df10fbc5d1ff0bcb174a"),
    "train-smallthinker-1chip.full": (
        (1, 16384, 28, 4, 128, True, 0),
        "e0a111cfcfadc3d02e98ce415f521b7cc6820dda94da81e05bd3d2e8f96b8691"),
    # the call at 64-wide heads and a scale of its own
    "train-granite4-1chip": (
        (1, 32768, 32, 8, 64, True, 0, 1 / 64),
        "605d62bfbb01b5fa336f68aad8eaf538d5a90a19ebacc6646a872ad62af451c8"),
}


def _cell_call(cell):
    """-> (the cell's flash call, summed in float32; its operands as bf16
    shapes)."""
    (b, s, h, kv, d, rule, rope, *scale), _ = _CELL_CALLS[cell]
    shapes = [(b, s, h, d), (b, s, kv, d), (b, s, kv, d)]
    if rope:
        shapes += [(b, s, h, rope), (b, s, 1, rope)]

    def call(q, k, v, *parts):
        return flash_attention(
            q, k, v, causal=rule, use_pallas=True,
            **dict(zip(("scale",), scale)),
            **dict(zip(("q_rope", "k_rope"), parts))).astype(jnp.float32).sum()

    return call, [jax.ShapeDtypeStruct(x, jnp.bfloat16) for x in shapes]


@pytest.mark.parametrize("cell", list(_CELL_CALLS))
def test_the_nine_cells_flash_calls_trace_to_what_they_were(cell):
    call, shapes = _cell_call(cell)
    assert _traced_digest(
        jax.value_and_grad(call, argnums=tuple(range(len(shapes)))),
        shapes) == _CELL_CALLS[cell][1]


# sha256 of the same twelve calls' FORWARD alone, the value and no gradient,
# as traced at PR 62: the backward's row statistics (PR 63) move every digest
# above and none of these. The forward kernel, its (o, lse [b, h, s, 1])
# outputs and its index maps are what six `*_fwd_roofline` queries of the
# benchmark pin.
_CELL_FORWARDS = {
    "train-1chip":
        "89446e7930763463b43c16899c7d1da7968f4f86cf92eada9d0bedd2c04d16f0",
    "train-4chip":
        "90627cb7fe8ceb9857c133d2276e50fd46322db8f76d42cb272123d4ee519915",
    "train-olmoe-1chip":
        "7b3e442f319d177fbeb25e8b249b089425e64459d4de69e293f0fa2403c7eba3",
    "train-joyai-1chip":
        "e90b79eccb2bcb726b2b8051a5af1265e6ad4723c8cc79da1654587d3e177197",
    "train-sdar-1chip":
        "2c1cefd7f1b978046802271cdabd3e3ec3c3b9fe36b26a644c9e73b728e97dc2",
    "train-ling-1chip":
        "e90b79eccb2bcb726b2b8051a5af1265e6ad4723c8cc79da1654587d3e177197",
    "train-nemotron3-1chip":
        "c24da12436acb76672dd9f2cde287d8525303e60943dc12644fd040637310f27",
    "train-laguna-1chip.window":
        "0cfaaa20b9a49b89392eba7c1fe34271e880962ed0750771a78a15882fc00354",
    "train-laguna-1chip.full":
        "f8bb8f8dbecdccac6ba66868e30b74e2fadd5ee17ec8ee76785bab0a601ad1e2",
    "train-smallthinker-1chip.window":
        "adcd922778933acc881a183586ef8bdf62ed06f2de1f4555505b892972959930",
    "train-smallthinker-1chip.full":
        "518f9e6ccdea708c01adf9ff4d72ed104c010da3d36d8b5afcac7c6c6d87f146",
    "train-granite4-1chip":
        "9fc1009c81cd6840cad1b7ec16542e00ed980cad4e8fc48c3e8c8fa7ef7c90f8",
}


@pytest.mark.parametrize("cell", list(_CELL_CALLS))
def test_the_cells_forward_calls_trace_to_the_parents(cell):
    call, shapes = _cell_call(cell)
    assert _traced_digest(call, shapes) == _CELL_FORWARDS[cell]


def _pallas_calls(jaxpr):
    """Every `pallas_call` equation of a jaxpr, those inside its
    sub-jaxprs (a `custom_vjp`'s, a `pjit`'s) too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("cell", [
    "train-joyai-1chip", "train-sdar-1chip", "train-laguna-1chip.window",
    "train-granite4-1chip"])
def test_no_column_reaches_a_kernel_of_the_flash_call(cell):
    """No operand of any Pallas call of the traced value and gradients is
    `f32[..., 1]`: lse and delta reach dq and dk/dv lane-dense. (The one
    such array left is the forward's lse OUTPUT, which the benchmark's
    forward queries match.)"""
    call, shapes = _cell_call(cell)
    (b, s, h, *_), _ = _CELL_CALLS[cell]
    traced = jax.make_jaxpr(jax.value_and_grad(
        call, argnums=tuple(range(len(shapes)))))(*shapes)
    calls = list(_pallas_calls(traced.jaxpr))
    assert len(calls) >= 3
    operands = [v.aval for eqn in calls for v in eqn.invars]
    assert not [a for a in operands if a.shape and a.shape[-1] == 1]
    # the rows: three float32 operands, one number a query each of dk/dv's
    # lse and delta and both in dq's one
    stats = [a for a in operands if a.dtype == jnp.float32]
    assert sorted(a.size // (b * h * s) for a in stats) == [1, 1, 2]
    columns = [v.aval for eqn in calls for v in eqn.outvars
               if v.aval.shape[-1] == 1]
    assert [a.shape for a in columns] == [(b, h, s, 1)]   # the forward's lse


def _row_stat_case(case):
    """-> (the call's operands, keywords) of a case of the test below."""
    (s_q, s_k, h, kv, d, rope), how = _ROW_STAT_CALLS[case]
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 6)
    args = [jax.random.normal(ks[0], (2, s_q, h, d)),
            jax.random.normal(ks[1], (2, s_k, kv, d)),
            jax.random.normal(ks[2], (2, s_k, kv, d))]
    if rope:
        how = dict(how, q_rope=jax.random.normal(ks[3], (2, s_q, h, rope)),
                   k_rope=jax.random.normal(ks[4], (2, s_k, 1, rope)))
    return args, how, jax.random.normal(ks[5], (2, s_q, h, d))


# (queries, keys, heads, KV heads, head dim, rotary part), the call's keywords
_ROW_STAT_CALLS = {
    # no multiple of block_q (128) nor of dk/dv's step
    "ragged": ((300, 300, 2, 2, 32, 0), dict(block_q=128, block_k=128)),
    # dk/dv's step (128) is half a block of queries: two rows a block
    "two-rows-a-block": ((600, 600, 2, 2, 32, 0),
                         dict(block_q=256, block_k=128)),
    # a sequence under one sub-tile: the block is the sequence, 100 lanes
    "short": ((100, 100, 2, 2, 32, 0), {}),
    "queries-at-the-keys-end": ((200, 456, 2, 2, 32, 0),
                                dict(block_q=128, block_k=128)),
    "gqa-8": ((256, 256, 8, 1, 32, 0), dict(block_q=128, block_k=128)),
    "in-parts-rope-64": ((256, 256, 4, 4, 32, 64),
                         dict(block_q=128, block_k=128)),
    "head-64": ((320, 320, 4, 2, 64, 0),
                dict(block_q=128, block_k=128, scale=1 / 64)),
    "block-diffusion": ((512, 512, 2, 1, 32, 0),
                        dict(mask=BlockDiffusion(256, 4), block_q=256,
                             block_k=256)),
    "window": ((640, 640, 2, 2, 32, 0),
               dict(mask=SlidingWindow(160), block_q=256, block_k=256)),
    "eva": ((256, 288, 2, 2, 32, 0),
            dict(mask=EvaWindows(256, 64, 8), block_q=128, block_k=128)),
    # more than eight rows of statistics a head
    "ten-rows": ((1280, 1280, 2, 1, 32, 0), dict(block_q=128, block_k=128)),
}


@pytest.mark.parametrize("case", list(_ROW_STAT_CALLS))
def test_flash_gradients_with_row_statistics_against_the_oracle(case):
    """The three gradients (five in parts) of sum(o * w), the kernels
    (interpreted) against the float32 oracle, at the shapes where a row of
    lse or delta can go wrong: the paddings to `block_q` and to dk/dv's step,
    a block of several rows, `seq_q != seq_k`, each rule."""
    args, how, w = _row_stat_case(case)
    parts = [how.pop(name) for name in ("q_rope", "k_rope") if name in how]

    def grads(**path):
        def loss(*a):
            return jnp.sum(flash_attention(
                *a[:3], **how, **path,
                **dict(zip(("q_rope", "k_rope"), a[3:]))) * w)

        with jax.default_matmul_precision("highest"):
            return jax.grad(loss, argnums=tuple(range(len(args + parts))))(
                *args, *parts)

    for got, want in zip(grads(interpret=True), grads(use_pallas=False)):
        assert got.shape == want.shape
        scale = float(jnp.abs(want).max())
        np.testing.assert_allclose(got / scale, want / scale, atol=2e-5)


@functools.lru_cache(maxsize=None)
def _d64_case(how):
    """A [2, 320, 8, 64] x [2, 320, 2, 64] causal call at a scale that is
    not 64 ** -0.5 -> o and the three gradients of sum(o * w)."""
    ks = jax.random.split(jax.random.PRNGKey(64), 4)
    q = jax.random.normal(ks[0], (2, 320, 8, 64))
    k = 4.0 * jax.random.normal(ks[1], (2, 320, 2, 64))
    v = jax.random.normal(ks[2], (2, 320, 2, 64))
    w = jax.random.normal(ks[3], q.shape)
    scale = {"kernels": 1 / 64, "oracle": 1 / 64, "sqrt_d": 1 / 8}[how]

    def call(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale,
                               block_q=128, block_k=128,
                               interpret=how == "kernels", use_pallas=False)

    with jax.default_matmul_precision("highest"):
        return (call(q, k, v),) + jax.grad(
            lambda *a: jnp.sum(call(*a) * w), argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("tensor", range(4), ids=["o", "dq", "dk", "dv"])
def test_flash_at_a_64_wide_head_with_a_scale_of_its_own(tensor):
    """The kernels (interpreted) against the oracle, forward and backward;
    the same oracle at 64 ** -0.5 is another function."""
    got, want = _d64_case("kernels")[tensor], _d64_case("oracle")[tensor]
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got / scale, want / scale, atol=2e-5)
    other = _d64_case("sqrt_d")[tensor]
    assert float(jnp.abs(other - want).max()) / scale > 1e-2


def test_a_64_wide_heads_blocks_are_reckoned_at_128_lanes_in_vmem():
    """What the v5e compiler refused at S 32,768, D 64: one KV head's K and
    V whole take a 128-wide head's room; a [rows, 1] column is counted as it
    is, so the limits the calls at 128-wide heads state did not move."""
    import importlib

    # (`ray_tpu.ops.flash_attention` the attribute is the function)
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    assert fa._in_vmem((1, 1, 32768, 64)) == fa._in_vmem((1, 1, 32768, 128))
    assert fa._in_vmem((1, 1, 512, 192)) == 512 * 256
    assert fa._in_vmem((1, 1, 512, 1)) == 512
    grads = jax.make_jaxpr(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, scale=1 / 64, use_pallas=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(
        jax.ShapeDtypeStruct((1, 32768, 32, 64), jnp.bfloat16),
        *[jax.ShapeDtypeStruct((1, 32768, 8, 64), jnp.bfloat16)] * 2)
    limits = [int(x) for x in re.findall(r"vmem_limit_bytes=(\d+)",
                                         str(grads))]
    assert len(limits) == 3 and min(limits) > 48 * 2**20


# --------------------------------------------------------------------------
# EVA: a rule over a key axis that is not the query axis (`EvaWindows`)
# --------------------------------------------------------------------------

def _dense_eva(length, window, chunk):
    """[length, length / chunk + length] bool, written from w(.) and c(.):
    the summaries' columns first."""
    n = np.arange(length)[:, None]
    j = np.arange(length)[None, :]
    c = np.arange(length // chunk)[None, :]
    return np.concatenate(
        [(c * chunk) // window < n // window,
         (j // window == n // window) & (j <= n)], axis=1)


@pytest.mark.parametrize("length, window, chunk", [
    (128, 32, 4), (96, 32, 4), (80, 32, 4), (256, 64, 8), (120, 24, 4),
    (1536, 512, 16)])
def test_eva_windows_rule_against_the_dense_mask(length, window, chunk):
    """`keep`, `kept` / `needed` and `tile` (in closed form) against the
    dense mask over EVERY tile of several sizes and alignments, some past
    either end; a last window that is partial; a window that is no power of
    two (the predicate divides where it cannot shift)."""
    rule = EvaWindows(length, window, chunk)
    n_sum = length // chunk
    s_k = n_sum + length
    dense = _dense_eva(length, window, chunk)
    np.testing.assert_array_equal(
        rule.keep(np.arange(length)[:, None] + n_sum, np.arange(s_k)[None]),
        dense)
    assert rule.kept(length, s_k) == (dense[:, n_sum:].sum(),
                                      dense[:, :n_sum].sum())
    assert rule.needed(length, s_k) == dense.sum()
    for nq, nk in ((8, 8), (16, 16), (32, 16), (7, 5), (64, 64), (128, 128)):
        for q0 in range(-nq, s_k + nq, nq):
            rows = np.arange(max(q0, n_sum), min(q0 + nq, s_k)) - n_sum
            for k0 in range(0, s_k + nk, nk):
                kept = dense[rows][:, k0:k0 + nk]
                want = (bool(kept.any()), bool(kept.all())) if kept.size \
                    else (False, False)
                assert tuple(map(bool, rule.tile(q0, nq, k0, nk))) == want, \
                    (q0, nq, k0, nk)


def test_eva_windows_rule_wants_its_own_lengths():
    rule = EvaWindows(128, 32, 4)
    for s_q, s_k in ((128, 128), (64, 160), (128, 161)):
        with pytest.raises(ValueError, match="summaries"):
            rule.needed(s_q, s_k)
    with pytest.raises(ValueError, match="whole chunks"):
        EvaWindows(130, 32, 4).needed(130, 162)


def test_eva_windows_schedule_at_the_cell_shape():
    """train-evabyte-1chip's call: 32,768 queries over [2,048 summaries ;
    32,768 bytes] in tiles of 512. A forward row of window w walks w // 4
    whole summary tiles, the one of which it sees w mod 4 of the four
    128-wide columns, and up to 4 tiles of its own window, the last its
    causal diagonal tile: no row has more than 8 steps, the forward's budget,
    but the 64 rows are 304 steps in all, and unrolled they ran 16 x slower
    on the v5e than as loops (PERF.md section 6, PR 57), so no plan of more
    than `_STATIC_STEPS` steps in all is unrolled: the three kernels walk
    their 304 tiles in loops, the 192 the rule keeps whole in bodies of 4
    and of 2 steps with no mask where a row's fill one (160), the others
    one an iteration under the mask (PR 60). dk/dv's longest row (a summary
    tile's) walks 60 query tiles."""
    rule = EvaWindows(32768, 2048, 16)
    assert rule.kept(32768, 34816) == (33_570_816, 31_457_280)
    assert rule.needed(32768, 34816) == 65_028_096
    assert CAUSAL.needed(32768, 32768) == 536_887_296
    plans = block_schedule(32768, 34816, 512, 512, rule)
    fwd, dkv = plans["fwd"], plans["dkv"]
    assert plans["dq"] is fwd
    assert max(map(len, fwd.rows)) == 8 == _STATIC_BUDGET["fwd"][1]
    assert len(fwd.tiles) == 304 > _STATIC_STEPS == 36
    assert not fwd.static and not dkv.static
    for plan in (fwd, dkv):
        assert (plan.steps_unmasked, plan.steps_masked,
                plan.steps_triangle) == (160, 144, 0)
        assert plan.steps_loop_body == 160 and plan.body == (4, 2)
        assert plan.steps_skipped == 64 * 68 - 304
        assert plan.executed_over_needed == pytest.approx(
            304 * 512 * 512 / 65_028_096)                      # 1.2255
    # 160 tiles of the windows' own bytes + 144 of summaries
    own = sum(1 for t in fwd.tiles if t[2] >= 2048)
    assert (own, len(fwd.tiles) - own) == (160, 144)
    assert max(map(len, dkv.rows)) == 60
    # two windows are under the cap and unrolled, four are not
    short = block_schedule(4096, 4352, 512, 512, EvaWindows(4096, 2048, 16))
    assert short["fwd"].static and len(short["fwd"].tiles) == 32
    assert not block_schedule(8192, 8704, 512, 512,
                              EvaWindows(8192, 2048, 16))["fwd"].static


_EVA_CALLS = {
    # (S, window, chunk, heads, head dim, blocks): the summaries' keys make
    # s_k > s_q and no multiple of the blocks
    "one_tile": (128, 32, 4, 2, 16, 512),
    "partial_last_window": (80, 32, 4, 2, 16, 512),
    "several_tiles_unrolled": (1024, 256, 16, 1, 128, 256),
    "loop_plans": (2560, 1280, 16, 1, 128, 128),
}


@functools.lru_cache(maxsize=None)
def _eva_case(case, how):
    s, window, chunk, h, d, block = _EVA_CALLS[case]
    ks = jax.random.split(jax.random.PRNGKey(57), 6)
    q, k, v, w = (jax.random.normal(key, (1, s, h, d)) for key in ks[:4])
    phi, mu = (jax.random.normal(key, (h, d)) for key in ks[4:])

    def loss(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
            argnums=(0, 1, 2, 3, 4))(q, k, v, phi / d ** 0.5, mu)

    if how == "definition":
        return loss(lambda *a: eva.eva_attention_reference(
            *a, window, chunk))
    return loss(lambda *a: eva.eva_attention(
        *a, window, chunk, block_q=block, block_k=block,
        interpret=how == "kernels"))


@pytest.mark.parametrize("tensor", range(6),
                         ids=["o", "dq", "dk", "dv", "dphi", "dmu"])
@pytest.mark.parametrize("how", ["oracle", "kernels"])
@pytest.mark.parametrize("case", sorted(_EVA_CALLS))
def test_eva_attention_matches_its_definition(case, how, tensor):
    """`ops/eva.eva_attention` (the pooling, then the flash call under
    `EvaWindows` over [summaries ; bytes], s_k > s_q) against the dense
    float32 definition, forward and every gradient, phi's and mu's through
    the summaries' rows of dk and dv among them; in `jnp` (the oracle path)
    and in the Pallas interpreter."""
    if (case, how) == ("loop_plans", "kernels"):
        s, window, chunk, *_ = _EVA_CALLS[case]
        plans = block_schedule(s, s + s // chunk, 128, 128,
                               EvaWindows(s, window, chunk))
        assert not plans["fwd"].static and not plans["dkv"].static
    got, want = _eva_case(case, how), _eva_case(case, "definition")
    got = (got[0],) + got[1]
    want = (want[0],) + want[1]
    scale = float(jnp.abs(want[tensor]).max())
    np.testing.assert_allclose(np.asarray(got[tensor]) / scale,
                               np.asarray(want[tensor]) / scale, atol=3e-5)


def test_eva_attention_counts_its_scores_by_kind():
    from ray_tpu._private import device_profiler

    q = jnp.zeros((1, 1024, 1, 128))
    before = device_profiler.snapshot()["counters"]
    jax.make_jaxpr(lambda q: eva.eva_attention(
        q, q, q, q[0, 0], q[0, 0], 256, 16))(q)
    after = device_profiler.snapshot()["counters"]
    moved = {k: after[k] - before.get(k, 0) for k in after
             if k.startswith("eva.")}
    # 4 windows of 256: 4 x 32,896 own bytes, 256 x 16 x (0 + 1 + 2 + 3)
    assert moved == {"eva.calls": 1, "eva.scores_local": 131_584,
                     "eva.scores_summary": 24_576}


def test_summarise_is_a_softmax_over_each_chunks_bytes():
    """A chunk whose phi . k_j is large at ONE byte is that byte's key plus
    mu and that byte's value; phi 0 is the plain mean."""
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 2, 8))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 2, 8))
    mu = jax.random.normal(jax.random.PRNGKey(3), (2, 8))
    k_sum, v_sum = eva.summarise(k, v, jnp.zeros((2, 8)), mu, 4)
    np.testing.assert_allclose(
        k_sum, k.reshape(1, 8, 4, 2, 8).mean(2) + mu, atol=1e-6)
    np.testing.assert_allclose(v_sum, v.reshape(1, 8, 4, 2, 8).mean(2),
                               atol=1e-6)
    peaked = k.at[0, 5].set(0.0).at[0, 5, :, 0].set(40.0)   # chunk 1, byte 1
    phi = jnp.zeros((2, 8)).at[:, 0].set(1.0)
    k_sum, v_sum = eva.summarise(peaked, v, phi, mu, 4)
    np.testing.assert_allclose(k_sum[0, 1], peaked[0, 5] + mu, atol=1e-4)
    np.testing.assert_allclose(v_sum[0, 1], v[0, 5], atol=1e-4)
    with pytest.raises(ValueError, match="whole chunks"):
        eva.summarise(k[:, :30], v[:, :30], phi, mu, 4)
