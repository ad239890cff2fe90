"""Inference benchmarks (VERDICT r1 #10): on-device decode tokens/sec at
full continuous-batching occupancy, plus the SERVING-level numbers that
actually face users — TTFT p50/p99 and steady-state tokens/sec under
Poisson arrivals through the full serve.llm stack (router, engine
replicas, streaming-generator token path).

Device benchmarks: both need a TPU and raise without one. The serving
benchmark's driver never touches jax — its replicas are worker processes
that demand the chips, build their own weights and report their own
device — so run each mode as its own process, with nothing else holding
the chips.

Run: python -m ray_tpu.inference.benchmarks            # engine decode
     python -m ray_tpu.inference.benchmarks serving    # serving TTFT/tput
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict


def benchmark_engine(*, max_batch: int = 8, max_len: int = 512,
                     new_tokens: int = 64,
                     decode_chunk: int = 32) -> Dict[str, Any]:
    import jax

    from ray_tpu._private.accelerators.tpu import hbm_peak_bytes_per_sec
    from ray_tpu.inference.engine import GenerationConfig, InferenceEngine
    from ray_tpu.models import llama

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"benchmark_engine is a device benchmark: found {dev.platform} "
            "devices, need a TPU")
    config = llama.LlamaConfig.small_1b()
    params = llama.init(config, jax.random.PRNGKey(0))
    eng = InferenceEngine(params, config, max_batch=max_batch,
                          max_len=max_len, decode_chunk=decode_chunk)
    gen = GenerationConfig(max_new_tokens=new_tokens)
    prompts = [[1 + (i % 31)] * 16 for i in range(max_batch)]

    # Warm up with the REAL shapes (compiles the fused generate_wave
    # program), then measure steady state: a full generate() is ONE
    # dispatch + one result transfer (engine.py generate_wave).
    eng.generate(prompts, gen)
    t0 = time.perf_counter()
    n_tokens = sum(len(toks) for toks in eng.generate(prompts, gen))
    # the fence lives inside generate(): the wave's tokens are device_get
    # before they reach these host lists, so the delta below covers
    # completed device work
    # raylint: disable=unfenced-device-timing
    dt = time.perf_counter() - t0

    # HBM bandwidth roofline (VERDICT r3 weak #1): every decode step reads
    # the bf16 params plus the live KV cache.
    param_bytes = config.num_params() * 2
    kv_bytes = (config.n_layers * max_batch * max_len
                * config.n_kv_heads * config.d_head * 2 * 2)
    roofline_tok_s = (hbm_peak_bytes_per_sec(dev.device_kind)
                      / (param_bytes + kv_bytes) * max_batch)
    return {
        "metric": "engine_decode_tokens_per_sec",
        "value": round(n_tokens / dt, 1),
        "unit": "tokens/s",
        "detail": {
            "model_params_m": round(config.num_params() / 1e6, 1),
            "max_batch": max_batch,
            "new_tokens_per_req": new_tokens,
            "platform": dev.platform,
            "device_kind": dev.device_kind,
            "n_dispatches": 1,
            "hbm_roofline_tokens_per_sec": round(roofline_tok_s, 1),
            # wall clock over the roofline: includes the one dispatch and
            # the prefill, so a lower bound on the decode kernel's share
            "roofline_frac": round(n_tokens / dt / roofline_tok_s, 3),
            "note": ("fused generate_wave: batched prefill + on-device "
                     "sampling + the whole decode loop in one compiled "
                     "program, timed on the host clock around it"),
        },
    }


def replica_stats(app_name: str, engine_deployment: str) -> list:
    """Every engine replica's `get_stats()`, which names the device it
    computes on (PagedInferenceEngine.device_report); raises unless each
    is a TPU. The driver's platform says nothing: replicas are other
    processes."""
    import ray_tpu

    controller = ray_tpu.get_actor("SERVE_CONTROLLER")
    replicas = ray_tpu.get(controller.get_replica_handles.remote(
        app_name, engine_deployment))
    stats = [ray_tpu.get(r.handle_request.remote("get_stats", (), {}),
                         timeout=60) for r in replicas]
    devices = [s["engine"]["device"] for s in stats]
    if any(d["platform"] != "tpu" for d in devices):
        raise RuntimeError(
            f"{engine_deployment}: a device benchmark's replicas must run "
            f"on a TPU, but report {devices}")
    return stats


def advertised_chips() -> int:
    """TPU chips the cluster can place actors on; raises when there are
    none — better than waiting for ever on a replica nobody can place."""
    import ray_tpu

    chips = int(ray_tpu.cluster_resources().get("TPU", 0))
    if chips < 1:
        raise RuntimeError(
            "device benchmark: the cluster advertises no TPU chips")
    return chips


def benchmark_serving(*, n_requests: int = 24,
                      arrival_rate_hz: float = 8.0,
                      max_new_tokens: int = 12,
                      prompt_len: int = 8) -> Dict[str, Any]:
    """Serving benchmark under OPEN-LOOP Poisson arrivals: requests fire
    on an exponential-gap schedule regardless of completions (closed-loop
    clients hide queueing collapse), stream through router + engine
    replicas, and the stats come from client-observed token arrival
    times. The perf trajectory this feeds tracks what users feel — TTFT
    and steady-state delivered tokens/sec — not just on-device decode.
    One replica per chip the cluster advertises."""
    import random
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_app

    def build():
        # runs inside the replica: weights are made on the replica's chip
        import jax

        from ray_tpu.inference.paged_engine import PagedInferenceEngine
        from ray_tpu.models import llama

        config = llama.LlamaConfig.small_1b()
        return PagedInferenceEngine(
            llama.init(config, jax.random.PRNGKey(0)), config, max_batch=8,
            max_len=128, block_size=16, decode_chunk=4)

    ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    num_replicas = advertised_chips()
    app = build_llm_app(
        build, name="llm_bench", num_replicas=num_replicas,
        default_config={"max_new_tokens": max_new_tokens},
        shed_queue_depth=10_000,  # measure queueing, don't shed it
        engine_actor_options={"resources": {"TPU": 1}})
    handle = serve.run(app, name="llm_bench")
    devices = [s["engine"]["device"]
               for s in replica_stats("llm_bench", "llm_bench_engine")]
    stream = handle.options(method_name="stream_tokens", stream=True)
    rng = random.Random(0)
    prompts = [[1 + rng.randrange(31) for _ in range(prompt_len)]
               for _ in range(n_requests)]
    # warm every replica's compiled programs out of the measurement
    warm = [threading.Thread(
        target=lambda p=p: list(stream.remote({"prompt": p})))
        for p in prompts[:num_replicas * 2]]
    for t in warm:
        t.start()
    for t in warm:
        t.join()

    results: list = [None] * n_requests

    def issue(i: int, prompt):
        t0 = time.perf_counter()
        first = None
        n = 0
        for _tok in stream.remote({"prompt": prompt}):
            if first is None:
                first = time.perf_counter()
            n += 1
        results[i] = (t0, first, time.perf_counter(), n)

    threads = []
    t_start = time.perf_counter()
    for i, prompt in enumerate(prompts):
        time.sleep(rng.expovariate(arrival_rate_hz))
        t = threading.Thread(target=issue, args=(i, prompt))
        t.start()
        threads.append(t)
    for t in threads:
        t.join()
    t_end = time.perf_counter()
    serve.shutdown()

    done = [r for r in results if r is not None and r[1] is not None]
    if len(done) < n_requests:
        raise RuntimeError(
            f"{n_requests - len(done)} of {n_requests} serving requests "
            "produced no token")
    ttfts = sorted((first - t0) * 1e3 for t0, first, _, _ in done)
    total_tokens = sum(n for _, _, _, n in done)

    def pct(p):
        return round(ttfts[min(len(ttfts) - 1,
                               int(p / 100 * len(ttfts)))], 2)

    return {
        "metric": "llm_serving_ttft_p50_ms",
        "value": pct(50),
        "unit": "ms",
        "detail": {
            "ttft_p99_ms": pct(99),
            "tokens_per_sec": round(total_tokens / (t_end - t_start), 1),
            "n_requests": len(done),
            "num_replicas": num_replicas,
            "arrival_rate_hz": arrival_rate_hz,
            "max_new_tokens": max_new_tokens,
            "platform": devices[0]["platform"],
            "device_kind": devices[0]["device_kind"],
            "replica_devices": devices,
            "note": ("open-loop Poisson arrivals through serve.llm "
                     "(router + continuous-batching engine replicas, "
                     "streaming token path); client-observed timings"),
        },
    }


if __name__ == "__main__":
    import sys

    from ray_tpu._private import compile_cache

    compile_cache.enable()
    if "serving" in sys.argv[1:]:
        print(json.dumps(benchmark_serving()))
    else:
        print(json.dumps(benchmark_engine()))
