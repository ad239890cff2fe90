"""Paged KV cache engine (PagedAttention layout; see
ray_tpu/inference/paged_engine.py): the paged forward's logits against the
plain forward, block accounting, many concurrent ragged streams on a small
pool, and recompute-preemption when the pool runs dry. (Greedy engine
output against naive full-forward decode: tests/test_inference.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference import GenerationConfig
from ray_tpu.inference.paged_engine import PagedInferenceEngine
from ray_tpu.models import llama


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32,
                           "remat": False})
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


_PARITY_CASES = [
    pytest.param(kv, qk, bs, None, id=f"kv{kv}-qknorm{int(qk)}-block{bs}")
    for kv in (4, 2) for qk in (False, True) for bs in (4, 16)
] + [pytest.param(2, True, 4, 5, id="kv2-qknorm1-block4-padded-tail")]


@pytest.mark.parametrize("n_kv_heads,qk_norm,block_size,short_len",
                         _PARITY_CASES)
def test_paged_cache_matches_full_forward(n_kv_heads, qk_norm, block_size,
                                          short_len):
    """Prefill 8 tokens then decode 4, one at a time, through the pool:
    every position's logits must match `llama.forward` over the whole
    sequence (no cache at all). With `short_len`, row 1's prompt is that
    many tokens inside the same padded [2, 8] prefill (its tail invalid),
    and it decodes from there."""
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32,
                           "remat": False, "n_kv_heads": n_kv_heads,
                           "qk_norm": qk_norm})
    params = llama.init(cfg, jax.random.PRNGKey(0))
    if qk_norm:  # init makes the scales 1: move them, or they test nothing
        for name, seed in (("q_norm", 3), ("k_norm", 4)):
            w = params["layers"][name]
            params["layers"][name] = w + 0.2 * jax.random.normal(
                jax.random.PRNGKey(seed), w.shape, w.dtype)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              cfg.vocab_size)
    full = np.asarray(llama.forward(params, toks, cfg))  # [2, 12, V]

    def close(got, want):
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=2e-4, atol=2e-4)

    n_pre = 8
    lens = np.array([n_pre, short_len or n_pre], np.int32)
    pool = llama.init_paged_kv_cache(cfg, n_blocks=9, block_size=block_size)
    # each row's blocks out of order and interleaved with the other's
    table = jnp.asarray([[3, 1, 5, 7], [2, 8, 4, 6]], jnp.int32)
    valid = jnp.arange(n_pre)[None, :] < lens[:, None]
    prefill = jnp.where(valid, toks[:, :n_pre], 0)
    logits, pool = llama.forward_with_paged_cache(
        params, prefill, pool, table, jnp.zeros(2, jnp.int32), cfg,
        valid=valid)
    for row in range(2):
        close(logits[row, :lens[row]], full[row, :lens[row]])
    for _ in range(4):
        step, pool = llama.forward_with_paged_cache(
            params, toks[np.arange(2), lens][:, None], pool, table,
            jnp.asarray(lens), cfg)
        close(step[:, 0], full[np.arange(2), lens])
        lens = lens + 1


def test_eight_concurrent_streams_small_pool(tiny):
    """>= 8 concurrent ragged streams through a pool HALF the dense
    reservation (the whole point of paging)."""
    cfg, params = tiny
    eng = PagedInferenceEngine(params, cfg, max_batch=8, max_len=64,
                               block_size=8)  # default pool: half dense
    assert eng.n_blocks - 1 < 8 * (64 // 8)
    prompts = [[1 + i] * (3 + 5 * (i % 4)) for i in range(12)]
    gen = GenerationConfig(max_new_tokens=10)
    out = eng.generate(prompts, gen)
    assert len(out) == 12 and all(len(o) == 10 for o in out)
    # pool fully reclaimed after the batch (released blocks may park in
    # the prefix-cache LRU, but every one must be allocatable again)
    assert eng.available_blocks() == eng.n_blocks - 1
    assert sorted(eng.free_slots) == list(range(8))


def test_preemption_by_recomputation(tiny):
    """A pool too small for all admitted requests must preempt the
    youngest (recompute) and still produce exactly the tokens a roomy
    pool produces."""
    cfg, params = tiny
    prompts = [[2, 4, 6], [1, 3, 5], [7, 8, 9]]
    gen = GenerationConfig(max_new_tokens=24)
    roomy = PagedInferenceEngine(params, cfg, max_batch=4, max_len=64,
                                 block_size=8, n_blocks=40)
    expected = roomy.generate(prompts, gen)
    assert roomy.preemptions == 0

    # 3 requests x (3 prompt + 24 new) tokens ~= 11 blocks of 8; give the
    # pool 8 usable blocks so growth mid-decode must preempt
    tight = PagedInferenceEngine(params, cfg, max_batch=4, max_len=64,
                                 block_size=8, n_blocks=9)
    got = tight.generate(prompts, gen)
    assert tight.preemptions > 0, "tight pool never preempted"
    assert got == expected
    assert tight.available_blocks() == tight.n_blocks - 1


def test_lone_request_shrinks_chunk_instead_of_preempting(tiny):
    cfg, params = tiny
    eng = PagedInferenceEngine(params, cfg, max_batch=2, max_len=64,
                               block_size=8, n_blocks=5, decode_chunk=16)
    out = eng.generate([[1, 2, 3]], GenerationConfig(max_new_tokens=16))
    assert len(out[0]) == 16
    assert eng.preemptions == 0
