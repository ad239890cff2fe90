"""Inference engine tests: greedy decode against the full forward pass,
bucketed prefill, continuous batching, sampling. (The paged cache's
logits against the full forward: tests/test_paged_engine.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.inference import GenerationConfig
from ray_tpu.inference.paged_engine import PagedInferenceEngine
from ray_tpu.inference.sampling import sample_token
from ray_tpu.models import llama


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    cfg = cfg.__class__(**{**cfg.__dict__, "dtype": jnp.float32,
                           "remat": False})
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.mark.parametrize("prompts,n_new,block_size", [
    ([[3, 17, 42, 9]], 6, 64),
    ([[1, 5, 9, 2], [3, 3, 7], [11, 4, 8, 2, 6]], 12, 8),
], ids=["one-prompt", "three-prompts-two-slots-block-8"])
def test_greedy_engine_matches_naive_decode(tiny, prompts, n_new,
                                            block_size):
    cfg, params = tiny
    # Naive: repeatedly run the full forward and take argmax.
    expected = []
    for prompt in prompts:
        seq = list(prompt)
        for _ in range(n_new):
            logits = llama.forward(
                params, jnp.asarray([seq], jnp.int32), cfg)
            seq.append(int(jnp.argmax(logits[0, -1])))
        expected.append(seq[len(prompt):])

    eng = PagedInferenceEngine(params, cfg, max_batch=2, max_len=64,
                               block_size=block_size)
    out = eng.generate(prompts, GenerationConfig(max_new_tokens=n_new))
    assert out == expected


def test_continuous_batching_many_requests(tiny):
    """More requests than slots: slots are recycled; every request gets
    exactly max_new_tokens tokens; per-request results are independent of
    batch composition."""
    cfg, params = tiny
    prompts = [[i + 1, i + 2, i + 3] for i in range(5)]
    eng = PagedInferenceEngine(params, cfg, max_batch=2, max_len=64)
    out = eng.generate(prompts, GenerationConfig(max_new_tokens=4))
    assert all(len(o) == 4 for o in out)

    # Same prompts one-at-a-time give identical greedy outputs.
    for i, p in enumerate(prompts):
        eng1 = PagedInferenceEngine(params, cfg, max_batch=1, max_len=64)
        solo = eng1.generate([p], GenerationConfig(max_new_tokens=4))
        assert solo[0] == out[i], f"request {i} differs under batching"


def test_eos_frees_slot(tiny):
    cfg, params = tiny
    eng = PagedInferenceEngine(params, cfg, max_batch=1, max_len=64)
    # Find what greedy emits first, then use it as "eos".
    probe = eng.generate([[5, 6, 7]], GenerationConfig(max_new_tokens=1))
    eos = probe[0][0]
    eng2 = PagedInferenceEngine(params, cfg, max_batch=1, max_len=64)
    out = eng2.generate(
        [[5, 6, 7]], GenerationConfig(max_new_tokens=16, eos_token_id=eos))
    assert out[0] == [eos]  # stopped immediately at eos
    assert eng2.free_slots == [0]


def test_prefill_bucketing(tiny):
    cfg, params = tiny
    eng = PagedInferenceEngine(params, cfg, max_batch=1, max_len=256,
                               prefill_buckets=(8, 32, 256))
    assert eng._bucket_for(5) == 8
    assert eng._bucket_for(8) == 8
    assert eng._bucket_for(9) == 32
    assert eng._bucket_for(250) == 256
    with pytest.raises(ValueError):
        eng._bucket_for(257)
    # Long and short prompts produce consistent greedy output regardless of
    # padding bucket.
    p = [7] * 20  # bucket 32
    out = eng.generate([p], GenerationConfig(max_new_tokens=3))
    eng2 = PagedInferenceEngine(params, cfg, max_batch=1, max_len=256,
                                prefill_buckets=(64, 256))
    out2 = eng2.generate([p], GenerationConfig(max_new_tokens=3))
    assert out[0] == out2[0]


def test_mixed_bucket_prompts(tiny):
    """Prompts spanning prefill buckets are admitted in bucket-grouped
    waves; per-request results must be identical to solo runs."""
    cfg, params = tiny
    prompts = [[3, 1, 4], [9] * 40, [2, 7], [5] * 70]
    eng = PagedInferenceEngine(params, cfg, max_batch=4, max_len=256,
                               prefill_buckets=(8, 64, 256))
    out = eng.generate(prompts, GenerationConfig(max_new_tokens=4))
    for i, p in enumerate(prompts):
        solo = PagedInferenceEngine(params, cfg, max_batch=1, max_len=256,
                                    prefill_buckets=(8, 64, 256))
        assert solo.generate(
            [p], GenerationConfig(max_new_tokens=4))[0] == out[i]


def test_eos_admits_waiting_request(tiny):
    """With more requests than slots and an EOS that fires, the freed
    slot must admit the waiting request (decode_chunk caps the fused run
    so admission stays responsive)."""
    cfg, params = tiny
    probe = PagedInferenceEngine(params, cfg, max_batch=1, max_len=64)
    eos = probe.generate([[5, 6, 7]],
                         GenerationConfig(max_new_tokens=1))[0][0]
    eng = PagedInferenceEngine(params, cfg, max_batch=1, max_len=64,
                               decode_chunk=4)
    out = eng.generate(
        [[5, 6, 7], [1, 2, 3]],
        GenerationConfig(max_new_tokens=16, eos_token_id=eos))
    assert out[0][-1] == eos
    assert len(out[1]) >= 1  # the waiting request ran
    assert eng.free_slots == [0]


def test_sampling_ops():
    key = jax.random.PRNGKey(0)
    logits = jnp.asarray([[1.0, 5.0, 2.0, 0.5]])
    # Greedy
    assert int(sample_token(logits, key)[0]) == 1
    # top_k=1 equals greedy even at high temperature
    assert int(sample_token(logits, key, temperature=5.0, top_k=1)[0]) == 1
    # top_p tiny keeps only the best token
    assert int(sample_token(logits, key, temperature=1.0, top_p=0.01)[0]) == 1
    # temperature sampling stays within the vocab and varies with key
    toks = {int(sample_token(logits, jax.random.PRNGKey(i),
                             temperature=2.0)[0]) for i in range(20)}
    assert toks.issubset({0, 1, 2, 3}) and len(toks) > 1


def test_llm_serve_deployment(ray_start_regular, tiny):
    """End-to-end: LLM deployment behind serve with concurrent requests."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import llm_deployment

    cfg, params = tiny

    def build():
        return PagedInferenceEngine(params, cfg, max_batch=2, max_len=64)

    app = llm_deployment(build, default_config={"max_new_tokens": 4})
    handle = serve.run(app, name="llm-app")
    try:
        refs = [handle.generate.remote([i + 1, i + 2]) for i in range(4)]
        outs = [r.result(timeout_s=120) for r in refs]
        assert all(len(o) == 4 for o in outs)
        # Deterministic greedy: same prompt -> same output.
        again = handle.generate.remote([1, 2]).result(timeout_s=120)
        assert again == outs[0]
    finally:
        serve.shutdown()


def test_tp_sharded_engine_matches_unsharded(tiny):
    """Decode over a tp=2 mesh (VERDICT r1 #10: sharded decode wired to the
    engine): params in TP layout, KV pool sharded on kv-heads — greedy
    output must match the single-device engine exactly."""
    from ray_tpu.inference.engine import shard_params_for_inference
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh

    cfg, params = tiny
    prompts = [[3, 17, 42, 9], [5, 7]]
    gen = GenerationConfig(max_new_tokens=5)
    expected = PagedInferenceEngine(params, cfg, max_batch=2,
                                    max_len=64).generate(prompts, gen)

    mesh = build_mesh(MeshConfig(tp=2), devices=jax.devices()[:2])
    sharded = shard_params_for_inference(params, cfg, mesh)
    eng = PagedInferenceEngine(sharded, cfg, max_batch=2, max_len=64,
                               mesh=mesh)
    out = eng.generate(prompts, gen)
    assert out == expected
