"""Headline benchmark: Llama training-step throughput on the local TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.

Metric: training tokens/sec/chip on an 8B-width-proxy Llama-family model
(true Llama-3-8B layer shapes at reduced depth, ~1.35B params; bf16,
flash-attention Pallas kernels, remat, donated buffers) at seq 2048.
The reference publishes no absolute model-training numbers
(BASELINE.md: `published: {}`), so vs_baseline is MFU relative to the
A100-class 40% MFU bar named in BASELINE.json's north-star
("≥A100-equivalent MFU"): vs_baseline = MFU / 0.40.

It measures the device or it fails: with no TPU it exits non-zero, and a
sub-benchmark that fails fails the run. A chip belongs to one process at
a time, so this process never imports jax; every device benchmark is a
child that owns the chips while it runs, one after another
(`python bench.py --train` is the headline child).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from functools import partial


def _run_child(argv: list, timeout: float, env: dict) -> dict:
    """Run one benchmark process and parse its last JSON line (every bench
    prints one JSON line; warnings/log noise may precede it). A child
    that fails, or prints none, fails the run."""
    r = subprocess.run([sys.executable, *argv], capture_output=True,
                       text=True, timeout=timeout, env=env)
    if r.returncode == 0:
        for line in reversed(r.stdout.strip().splitlines()):
            line = line.strip()
            if line.startswith("{"):
                return json.loads(line)
    raise RuntimeError(
        f"benchmark {' '.join(argv)} failed (exit {r.returncode}):\n"
        f"{r.stderr[-2000:]}")


def _device_benches(chips: int) -> dict:
    """The benchmarks that compute on the chips, each in a process of its
    own, in the ambient (accelerator) environment."""
    env = dict(os.environ)
    run = partial(_run_child, env=env)
    out = {}
    eng = run(["-m", "ray_tpu.inference.benchmarks"], 900)
    out["engine_decode_tokens_per_sec"] = eng["value"]
    out["engine_model_params_m"] = eng["detail"]["model_params_m"]
    out["engine_decode"] = eng["detail"]
    if chips > 1:
        # Measured multi-device SPMD step (ISSUE 7): per-chip tokens/sec
        # over an (dp, fsdp, tp) mesh + scaling efficiency vs the
        # 1-device step at the same per-chip batch.
        mc = run(["-m", "ray_tpu.train.spmd_bench"], 1800)
        out["train_multichip_tokens_per_sec_per_chip"] = mc["value"]
        out["train_scaling_efficiency"] = mc["detail"]["scaling_efficiency"]
        out["train_multichip_detail"] = mc["detail"]
    # prefix-cache TTFT: shared-system-prompt hit vs cold
    p = run(["-m", "ray_tpu.serve.benchmarks", "prefix"],
            900)["llm_prefix_ttft"]
    out["llm_prefix_ttft_cold_ms"] = p["cold_p50_ms"]
    out["llm_prefix_ttft_hit_ms"] = p["hit_p50_ms"]
    out["llm_prefix_ttft_detail"] = p
    # serving-level LLM numbers (TTFT + delivered tokens/sec under
    # Poisson arrivals through serve.llm) so the perf trajectory tracks
    # serving, not just on-device decode
    lv = run(["-m", "ray_tpu.inference.benchmarks", "serving"], 900)
    out["llm_serving_ttft_p50_ms"] = lv["value"]
    out["llm_serving_ttft_p99_ms"] = lv["detail"]["ttft_p99_ms"]
    out["llm_serving_tokens_per_sec"] = lv["detail"]["tokens_per_sec"]
    out["llm_serving_detail"] = lv["detail"]
    return out


def _host_plane_benches() -> dict:
    """Host-side subsystems (rllib env-steps/s, serve RPS/p50/p99, the
    object plane): they compute nothing on the device, so their children
    are held to the CPU backend and stay off the chips."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    run = partial(_run_child, env=env)
    out = {}
    rl = run(["-m", "ray_tpu.rllib.benchmarks"], 600)
    out["rllib_env_steps_per_sec"] = rl["value"]
    out["rllib_env_steps_detail"] = rl.get("detail", {})
    # ISSUE 14 decoupled RL dataflow: learner-consumed env-steps/sec
    # through the bounded sample queue at >=2 rollout-worker counts —
    # a measured scaling curve, not a single-number plateau
    rd = run(["-m", "ray_tpu.rllib.benchmarks", "decoupled"], 900)
    out["rllib_decoupled_env_steps_per_sec"] = rd["value"]
    out["rllib_decoupled_scaling"] = rd["detail"].get("scaling")
    out["rllib_decoupled_detail"] = rd.get("detail", {})
    sv = run(["-m", "ray_tpu.serve.benchmarks", "classic"], 600)
    out["serve_http_rps"] = sv["serve_http"]["rps"]
    out["serve_http_p50_ms"] = sv["serve_http"]["p50_ms"]
    out["serve_http_p99_ms"] = sv["serve_http"]["p99_ms"]
    out["serve_handle_rps"] = sv["serve_handle"]["rps"]
    # the ISSUE 6 serving gate: max rps HELD at a p99 bound (not peak
    # rps), through the sharded proxy
    s = run(["-m", "ray_tpu.serve.benchmarks", "sustained"],
            600)["serve_http_sustained"]
    out["serve_http_sustained_rps"] = s["rps"]
    out["serve_http_sustained_p99_ms"] = s["p99_ms"]
    out["serve_http_sustained_detail"] = s
    # ISSUE 13 object/data plane: put/get bandwidth through the shm store
    # (numpy AND jax.Array — the typed wire keeps them within 1.2× of
    # each other) + the input-pipeline overlap fraction of the prefetched
    # iter_jax_batches feed
    dp = run(["-m", "ray_tpu._private.dataplane_bench"], 600)
    out["object_put_gbps"] = dp["detail"]["object_put_gbps"]
    out["object_get_gbps"] = dp["detail"]["object_get_gbps"]
    out["input_pipeline_overlap_frac"] = (
        dp["detail"]["input_pipeline_overlap_frac"])
    out["dataplane_detail"] = dp["detail"]
    return out


def train_headline() -> dict:
    """The headline cell, in this process: needs the TPU, raises without."""
    from ray_tpu._private import compile_cache

    compile_cache.enable()
    import jax
    import numpy as np
    import optax

    from ray_tpu._private.accelerators.tpu import bf16_peak_flops_per_chip
    from ray_tpu._private.device_profiler import (
        get_profiler,
        install_compile_listener,
        span,
    )
    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import LogicalAxisRules, logical_sharding
    from ray_tpu.train.step import init_train_state, make_train_step

    # arm compile telemetry BEFORE the first trace so the step program's
    # XLA compile lands in compile_s (ISSUE 15)
    install_compile_listener()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"bench.py measures the device: found {devices[0].platform} "
            "devices, need a TPU")
    n_devices = len(devices)
    peak_flops = bf16_peak_flops_per_chip(devices[0].device_kind)

    # 8B-width proxy (VERDICT r1 #1): true Llama-3-8B layer shapes
    # (d_model=4096, d_ff=14336, 32 heads / 8 kv heads x 128) at reduced
    # depth so params+AdamW state fit one 16 GB v5e chip. Per-layer
    # arithmetic intensity — the thing MFU depends on — matches the 8B
    # target; vocab reduced to 32k to keep the embedding from dominating
    # the HBM budget at depth. Chunked CE avoids materializing [B,S,V]
    # fp32 logits.
    cfg = llama.LlamaConfig(
        vocab_size=32_000, d_model=4096, n_layers=5, n_heads=32,
        n_kv_heads=8, d_head=128, d_ff=14_336, max_seq_len=2048,
        loss_chunk_size=1024,
    )
    # Per-chip batch of 4: global batch scales with the dp width so the
    # batch dim always divides the mesh (fixed global batch would fail
    # device_put on slices wider than 8 chips).
    batch, seq, steps = 4 * n_devices, 2048, 20

    mesh = build_mesh(MeshConfig(dp=n_devices))
    rules = LogicalAxisRules()
    opt = optax.adamw(3e-4, weight_decay=0.0)
    state, shardings = init_train_state(
        partial(llama.init, cfg), opt, llama.param_logical_axes(cfg),
        mesh, jax.random.PRNGKey(0), rules,
    )
    bs = logical_sharding(mesh, ("batch", "seq"), rules)
    step = make_train_step(
        partial(llama.loss_fn, config=cfg, mesh=mesh, rules=rules),
        opt, shardings, batch_sharding={"inputs": bs, "targets": bs},
    )
    toks = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size
    )
    b = {
        "inputs": jax.device_put(toks[:, :-1], bs),
        "targets": jax.device_put(toks[:, 1:], bs),
    }

    # Warmup/compile. The fence everywhere below is the host transfer of
    # the loss (float()): it cannot return before the step has run.
    state, m = step(state, b)
    float(m["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, b)
    float(m["loss"])
    dt = (time.perf_counter() - t0) / steps

    tokens_per_step = batch * seq
    tokens_per_sec_per_chip = tokens_per_step / dt / n_devices
    flops_tok = llama.flops_per_token(cfg, seq)
    mfu = flops_tok * tokens_per_sec_per_chip / peak_flops

    # Phase attribution of the train step: a short segment after the
    # headline timing, each phase a span that ends at its fence, so the
    # detail says whether the step is input-starved (input_wait/h2d) or
    # device-bound (device_execute), and how much of this process's wall
    # went to XLA compiles. The headline loop above stays as it is.
    prof = get_profiler("train")
    host_inputs = np.asarray(toks[:, :-1])
    host_targets = np.asarray(toks[:, 1:])
    for _ in range(5):
        with span("bench.input") as s_in:
            # host-side batch production (the input pipeline's share)
            hb = {"inputs": np.array(host_inputs),
                  "targets": np.array(host_targets)}
        with span("bench.h2d") as s_h2d:
            b2 = {k: jax.device_put(v, bs) for k, v in hb.items()}
            jax.block_until_ready(b2)
        with span("bench.step") as s_dev:
            state, m2 = step(state, b2)
            float(m2["loss"])  # the fence
        prof.record_step({"input_wait": s_in.seconds, "h2d": s_h2d.seconds,
                          "device_execute": s_dev.seconds},
                         tokens=tokens_per_step)
    phase_rep = prof.report(emit_event=False)

    detail = {
        "model_params_m": round(cfg.num_params() / 1e6, 1),
        "seq_len": seq,
        "global_batch": batch,
        "step_time_ms": round(dt * 1e3, 2),
        "mfu": round(mfu, 4),
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_devices": n_devices,
        "loss": round(float(m["loss"]), 4),
        # device-plane phase attribution of the train step (ISSUE 15)
        "input_wait_frac": phase_rep.get("input_wait_frac", 0.0),
        "device_frac": phase_rep.get("device_execute_frac", 0.0),
        "compile_s": round(
            phase_rep.get("compile_process", {}).get("compile_s", 0.0), 3),
        "train_step_phases": {
            k: v for k, v in phase_rep.items()
            if k not in ("recent_steps", "hbm")
        },
        "hbm": phase_rep.get("hbm", {}),
        # The north-star names "tokens/s/chip @ 8B". 16 GB of HBM cannot
        # hold 8B params + AdamW state, so the bench model keeps the TRUE
        # Llama-3-8B layer width (d_model 4096, d_ff 14336, 32h/8kv) at
        # reduced depth: per-layer arithmetic intensity — what MFU depends
        # on — matches the 8B target; depth is a proxy.
        "model_proxy": {"north_star": "llama3-8b", "width_match": True,
                        "depth": int(cfg.n_layers), "full_depth": 32},
    }
    return {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec_per_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 4),
        "detail": detail,
    }


def main() -> None:
    if sys.argv[1:] == ["--train"]:
        print(json.dumps(train_headline()))
        return
    from ray_tpu._private import compile_cache
    from ray_tpu._private.accelerators.tpu import count_local_chips

    chips = count_local_chips()
    if not chips:
        sys.exit("bench.py measures the device: this host has no TPU chips "
                 "(no /dev/accel* or /dev/vfio/<n>); nothing was run")
    compile_cache.enable()  # one cache for every child
    result = _run_child([os.path.abspath(__file__), "--train"], 1800,
                        dict(os.environ))
    result["detail"].update(_device_benches(chips))
    result["detail"].update(_host_plane_benches())
    print(json.dumps(result))


if __name__ == "__main__":
    main()
