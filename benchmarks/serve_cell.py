"""The `serve` kind of cell: `build_llm_app` -> `serve.run` -> HTTP proxy,
with the benchmark's own `build_engine`, as `chip_smoke.serve_phase` drives
it; closed-loop SSE clients from `loadgen.py` in this (jax-free) process.

`build_engine` runs inside the replica, the only process that opens the
chip. It makes the weights on the device from the seed in one jitted call,
checks the model program against `reference.py`, drives every prefill wave
and decode chunk the cell's traffic can reach through the engine's own
service loop (so nothing compiles in the window), and leaves a watcher
thread that traces a few seconds when the parent asks (a file appears):
only the process that holds the chip can trace it.
"""

from __future__ import annotations

import functools
import json
import os
import time

# max |program logit - float32 reference logit| over max |reference
# logit|, over the compared positions. bf16 weights and activations
# through 16 layers land at ~1e-2 (measured on the v5e: see PERF.md); an
# 8-bit float would be off by several times the bound, float32 activations
# would sit ~10x under it.
LOGIT_TOLERANCE = 4e-2
REPLICA_START_TIMEOUT_S = 900.0


def _write(path: str, obj) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)


def check_against_reference(llama, reference, params, model, model_fields,
                            key, block: int, prefill: int, steps: int):
    """Prefill `prefill` seeded tokens, then `steps` single-token decode
    steps (teacher-forced with the same seeded sequence) through the
    program's paged-cache forward on a small scratch pool; compare every
    produced logit row with the reference's full forward pass."""
    import jax
    import jax.numpy as jnp

    n_blocks = 2 + -(-(prefill + steps) // block)
    pool = llama.init_paged_kv_cache(model, n_blocks, block)
    table = jnp.arange(1, n_blocks, dtype=jnp.int32)[None, :]
    seq = jax.random.randint(key, (prefill + steps,), 1, model.vocab_size)
    fwd = jax.jit(functools.partial(llama.forward_with_paged_cache,
                                    config=model))
    logits, pool = fwd(params, seq[None, :prefill], pool, table,
                       jnp.zeros((1,), jnp.int32))
    rows = [logits[0, -1]]
    for i in range(steps):
        logits, pool = fwd(params, seq[None, prefill + i:prefill + i + 1],
                           pool, table,
                           jnp.full((1,), prefill + i, jnp.int32))
        rows.append(logits[0, -1])
    want = reference.logits(params, seq, model_fields)[prefill - 1:]
    got = jnp.stack(rows)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def warm_up(engine, gen_cls, waves, chunks, vocab: int) -> None:
    """Drive the engine's own service loop: one admission wave per
    (rows, prompt tokens) in `waves` (max_new 1: prefill only), then one
    lone request per decode chunk size in `chunks`."""
    import random

    rng = random.Random(0)
    jobs = [[(f"w{i}-{r}", [rng.randrange(1, vocab) for _ in range(n)], 1)
             for r in range(rows)] for i, (rows, n) in enumerate(waves)]
    jobs += [[(f"d{c}", [rng.randrange(1, vocab) for _ in range(8)], 1 + c)]
             for c in chunks]
    for job in jobs:
        pending = [job]

        def feed(_block, pending=pending):
            return (pending.pop() if pending else []), [], False

        want = sum(n for _, _, n in job)
        stream = engine.serve_stream(feed, gen_cls())
        got = 0
        for _req, token, _done in stream:
            if token is None:
                raise RuntimeError(
                    f"warm-up request refused: {engine.abort_reasons}")
            got += 1
            if got == want:
                break
        stream.close()


def build_engine(cfg):
    import glob
    import importlib
    import threading

    import jax

    from benchmarks import reduce_trace
    from ray_tpu._private.device_profiler import (
        compile_stats,
        install_compile_listener,
    )
    from ray_tpu.inference.engine import GenerationConfig
    from ray_tpu.inference.paged_engine import PagedInferenceEngine

    t_enter = time.time()
    install_compile_listener()
    # the model module's part of the contract: its config class (named
    # in the configuration), `init`, `init_paged_kv_cache`,
    # `forward_with_paged_cache`
    llama = importlib.import_module(cfg["model_module"])
    reference = importlib.import_module(cfg["reference_module"])
    model = getattr(llama, cfg["config_class"])(**cfg["model"])
    e, seed = cfg["engine"], cfg["seed"]
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    params = jax.jit(functools.partial(llama.init, model))(key)
    jax.block_until_ready(params)
    t_weights = time.time()
    check = e["reference_check"]
    logit_err = check_against_reference(
        llama, reference, params, model, cfg["model"],
        jax.random.fold_in(key, 2), e["block_size"], check["prefill_tokens"],
        check["decode_steps"])
    t_check = time.time()
    engine = PagedInferenceEngine(
        params, model, max_batch=e["max_batch"], max_len=e["max_len"],
        block_size=e["block_size"], n_blocks=e["n_blocks"],
        prefill_buckets=tuple(e["prefill_buckets"]),
        decode_chunk=e["decode_chunk"],
        forward_with_paged_cache=llama.forward_with_paged_cache,
        init_paged_kv_cache=llama.init_paged_kv_cache)
    warm_up(engine, GenerationConfig, cfg["warm_waves"], cfg["warm_chunks"],
            model.vocab_size)
    dev = jax.devices()[0]
    _write(os.path.join(cfg["out_dir"], "engine_ready.json"), {
        "logit_rel_err": logit_err,
        "correct": bool(logit_err <= LOGIT_TOLERANCE),
        "platform": dev.platform, "kind": dev.device_kind,
        "devices": len(jax.devices()),
        "weights_s": t_weights - t_enter, "check_s": t_check - t_weights,
        "warmup_s": time.time() - t_check,
        "compiles": compile_stats(),
    })

    def tracer():
        ask = os.path.join(cfg["out_dir"], "trace.ask")
        while not os.path.exists(ask):
            time.sleep(0.05)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1
        jax.profiler.start_trace(cfg["trace_dir"], profiler_options=opts)
        time.sleep(cfg["trace_seconds"])
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(
            cfg["trace_dir"], "plugins", "profile", "*", "*.xplane.pb"))
        traced = reduce_trace.reduce_xplane(paths[0], cfg["trace_queries"]) \
            if paths else None
        _write(os.path.join(cfg["out_dir"], "trace.json"), traced)

    if cfg["trace_dir"]:
        threading.Thread(target=tracer, name="bench-tracer",
                         daemon=True).start()
    return engine


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float))
            and isinstance(before.get(k), (int, float))}


def run(ctx: dict) -> dict:
    import ray_tpu
    from benchmarks import loadgen
    from ray_tpu import serve
    from ray_tpu._private.rpc import find_free_port
    from ray_tpu.serve.llm import build_llm_app

    config, traffic, out_dir = ctx["config"], ctx["traffic"], ctx["out_dir"]
    e = config["engine"]
    buckets = sorted(e["prefill_buckets"])
    sizes = loadgen.request_sizes(traffic, e["block_size"])
    if max(sizes) > buckets[-1] or max(sizes) + max(
            traffic["answer_tokens"]["range"]) >= e["max_len"]:
        raise ValueError("the mix's longest request does not fit the "
                         "engine's buckets or max_len")
    reached = sorted({next(b for b in buckets if n <= b) for n in sizes})
    # callers do not coordinate: a wave can hold a row from every one
    rows = traffic["clients"]
    chunks, c = [], 1
    while c <= e["decode_chunk"]:
        chunks.append(c)
        c *= 2
    cfg = {
        "model": ctx["model"], "model_module": config["program"]["module"],
        "config_class": config["program"]["config_class"],
        "reference_module": "benchmarks." + config["reference"],
        "engine": e, "seed": ctx["seed"], "out_dir": out_dir,
        # a wave's program is keyed by (rows, bucket): a prompt one token
        # under the bucket's edge reaches it
        "warm_waves": [(r, b - 1) for b in reached
                       for r in range(1, min(rows, e["max_batch"]) + 1)],
        "warm_chunks": chunks,
        "trace_dir": ctx["trace_dir"], "trace_queries": ctx["trace_queries"],
        "trace_seconds": config["trace_seconds"],
    }
    port = find_free_port()
    options = None if ctx["rehearse"] else {"resources": {"TPU": 1}}
    app = build_llm_app(
        functools.partial(build_engine, cfg), name="llm",
        num_replicas=config["replicas"],
        default_config={"max_new_tokens": 64},
        engine_actor_options=options)
    serve.run(app, name="llm", http_port=port)
    controller = ray_tpu.get_actor("SERVE_CONTROLLER")

    def replicas():
        return ray_tpu.get(controller.get_replica_handles.remote(
            "llm", "llm_engine"))

    # serve.run returns once ONE replica is up; wait for all of them
    deadline = time.monotonic() + REPLICA_START_TIMEOUT_S
    while len(replicas()) < config["replicas"]:
        if time.monotonic() > deadline:
            raise RuntimeError("not every replica came up")
        time.sleep(0.5)

    def stats():
        (replica,) = replicas()  # one replica: see PERF.md, open questions
        return ray_tpu.get(replica.handle_request.remote(
            "get_stats", (), {}), timeout=60)["engine"]

    marks = {}

    def on_window(which):
        marks[which] = stats()
        if which == "open":
            marks["t_window_wall"] = time.time()
            if ctx["trace_dir"]:
                # trace from a few seconds into the window
                marks["ask_at"] = time.time() + config["trace_after_s"]

    def ask_trace():
        while "ask_at" not in marks:
            time.sleep(0.05)
        time.sleep(max(0.0, marks["ask_at"] - time.time()))
        open(os.path.join(out_dir, "trace.ask"), "w").close()

    if ctx["trace_dir"]:
        import threading

        threading.Thread(target=ask_trace, daemon=True).start()
    client = loadgen.run_closed(traffic, ctx["seed"], ctx["seconds"], port,
                                ctx["model"]["vocab_size"], on_window)
    traced = None
    if ctx["trace_dir"]:
        path = os.path.join(out_dir, "trace.json")
        deadline = time.monotonic() + 120
        while not os.path.exists(path) and time.monotonic() < deadline:
            time.sleep(0.2)
        with open(path) as f:
            traced = json.load(f)
    serve.shutdown()
    with open(os.path.join(out_dir, "engine_ready.json")) as f:
        ready = json.load(f)
    before, after = marks["open"], marks["close"]
    phases = _delta(after["device_phases"].get("phase_seconds", {}),
                    before["device_phases"].get("phase_seconds", {}))
    prefix = _delta(after["prefix_cache"], before["prefix_cache"])
    engine_stats = {
        "compiles_in_window": after["compile"]["compiles"]
        - before["compile"]["compiles"],
        "preemptions": after["preemptions"] - before["preemptions"],
        "prefix_hit_tokens": prefix.get("hit_tokens", 0),
        "phase_seconds": phases,
        "peak_bytes_in_use": after["device"].get("peak_bytes_in_use", 0),
        "ready": ready,
    }
    if client["errors"]:
        print("client errors:", client["errors"][:5], flush=True)
    return {
        "t_window_wall": marks["t_window_wall"],
        "correct": bool(ready["correct"] and client["failed"] == 0
                        and client["attempted"] > 0
                        and not client["errors"]),
        "attempted": client["attempted"], "failed": client["failed"],
        "readings": {"client": client, "stats": engine_stats,
                     "trace": traced},
        "checks": {k: ready[k] for k in (
            "logit_rel_err", "weights_s", "check_s", "warmup_s")},
        "device": {"platform": after["device"]["platform"],
                   "kind": after["device"]["device_kind"],
                   "count": ready["devices"],
                   "memory_peak_bytes": after["device"].get(
                       "peak_bytes_in_use", 0)},
    }
