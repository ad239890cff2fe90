"""Measured multi-device SPMD training-step benchmark.

Runs the 8B-width proxy step (bench.py's shape) as the 1-device jit step
and as the pjit step over a named (dp, fsdp, tp) mesh spanning
`n_devices`, both at the SAME per-chip batch — the 1-device program at n
times the batch does not fit the chip it is the baseline for — and
reports measured per-chip tokens/sec, per-chip MFU and scaling efficiency
vs the 1-device step. (That the mesh program is a pure re-partitioning of
the 1-device math is pinned by tests/test_spmd_trainer.py on one shared
batch, not here.)

A device benchmark: it needs a TPU and raises without one. One process
drives all the chips, so nothing else on the host may hold them.

    python -m ray_tpu.train.spmd_bench [--n-devices N]

Prints ONE JSON line:
    {"metric": "train_multichip_tokens_per_sec_per_chip", "value": ...,
     "detail": {..., "scaling_efficiency": ...}}
"""

from __future__ import annotations

import argparse
import json
import sys
from functools import partial
from typing import Dict, List, Tuple


def axis_plan(n_devices: int) -> Dict[str, int]:
    """Split n devices over the (dp, fsdp, tp) named mesh, model axes
    first (tp rides the fastest links, then fsdp shards params, remainder
    is pure data parallel): 8 -> dp=2, fsdp=2, tp=2; 4 -> fsdp=2, tp=2;
    2 -> tp=2; odd prime counts fall back to pure dp."""
    plan = {"dp": 1, "fsdp": 1, "tp": 1}
    rest = n_devices
    for axis in ("tp", "fsdp"):
        if rest % 2 == 0:
            plan[axis] = 2
            rest //= 2
    plan["dp"] = rest
    return plan


def _timed_steps(step, state, batch, steps: int,
                 profiler=None) -> Tuple[float, List[float]]:
    """Wall time per step + the loss trajectory. The fence is the host
    transfer of each step's loss (float()). With a DeviceStepProfiler each
    step leaves a record of its device_execute phase (and of any compile
    it triggered)."""
    from ray_tpu._private.device_profiler import span

    losses = []
    state, m = step(state, batch)  # warmup/compile
    losses.append(float(m["loss"]))
    with span("spmd_bench.steps", steps=steps) as timed:
        for _ in range(steps):
            with span("spmd_bench.step") as sp:
                state, m = step(state, batch)
                # the float() host transfer is the fence
                losses.append(float(m["loss"]))
            if profiler is not None:
                profiler.record_step({"device_execute": sp.seconds})
    dt = timed.seconds / steps
    del state
    return dt, losses


def run(n_devices: int, steps: int = 8) -> dict:
    import jax
    import optax

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import LogicalAxisRules, logical_sharding
    from ray_tpu.train.step import init_train_state, make_train_step

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"spmd_bench is a device benchmark: found {devices[0].platform} "
            "devices, need a TPU")
    if len(devices) < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, found {len(devices)}")
    devices = devices[:n_devices]

    # Same 8B-width proxy as the headline bench: true Llama-3-8B layer
    # shapes at reduced depth; per-layer arithmetic intensity matches the
    # 8B target.
    cfg = llama.LlamaConfig(
        vocab_size=32_000, d_model=4096, n_layers=5, n_heads=32,
        n_kv_heads=8, d_head=128, d_ff=14_336, max_seq_len=2048,
        loss_chunk_size=1024,
    )
    per_chip_batch, seq = 4, 2048
    from ray_tpu._private.accelerators.tpu import bf16_peak_flops_per_chip

    peak_flops = bf16_peak_flops_per_chip(devices[0].device_kind)

    plan = axis_plan(n_devices)
    rules = LogicalAxisRules()
    opt = optax.adamw(3e-4, weight_decay=0.0)

    def measure(mesh, profiler=None) -> Tuple[float, List[float]]:
        batch = per_chip_batch * mesh.size
        toks = jax.random.randint(
            jax.random.PRNGKey(1), (batch, seq + 1), 0, cfg.vocab_size)
        state, shardings = init_train_state(
            partial(llama.init, cfg), opt, llama.param_logical_axes(cfg),
            mesh, jax.random.PRNGKey(0), rules)
        bs = logical_sharding(mesh, ("batch", "seq"), rules)
        step = make_train_step(
            partial(llama.loss_fn, config=cfg, mesh=mesh, rules=rules),
            opt, shardings, batch_sharding={"inputs": bs, "targets": bs})
        b = {"inputs": jax.device_put(toks[:, :-1], bs),
             "targets": jax.device_put(toks[:, 1:], bs)}
        return _timed_steps(step, state, b, steps, profiler=profiler)

    # Phase records of the MESH program's steps, visible to `ray-tpu
    # profile --device` via the registry.
    from ray_tpu._private.device_profiler import get_profiler

    flops_tok = llama.flops_per_token(cfg, seq)
    tokens_per_step = per_chip_batch * n_devices * seq
    prof_n = get_profiler("train_spmd")
    prof_n.reset()

    # The same per-chip batch through both programs: first the single-chip
    # baseline, then the mesh program over all n devices.
    from ray_tpu._private.device_profiler import compile_stats

    dt_1, losses_1 = measure(build_mesh(MeshConfig(), devices=devices[:1]))
    compile_before = compile_stats()
    dt_n, losses_n = measure(build_mesh(MeshConfig(**plan), devices=devices),
                             profiler=prof_n)
    compile_after = compile_stats()

    per_chip_1 = per_chip_batch * seq / dt_1  # 1 device
    per_chip_n = tokens_per_step / dt_n / n_devices

    detail = {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "n_devices": n_devices,
        "mesh_axes": plan,
        "model_params_m": round(cfg.num_params() / 1e6, 1),
        "seq_len": seq,
        "per_chip_batch": per_chip_batch,
        "steps": steps,
        "step_time_ms_1dev": round(dt_1 * 1e3, 2),
        "step_time_ms_ndev": round(dt_n * 1e3, 2),
        "tokens_per_sec_per_chip_1dev": round(per_chip_1, 1),
        "mfu_1dev": round(flops_tok * per_chip_1 / peak_flops, 4),
        "mfu": round(flops_tok * per_chip_n / peak_flops, 4),
        # per-chip throughput retained going 1 -> n chips at a fixed
        # per-chip batch (1.0 = perfect linear scaling)
        "scaling_efficiency": round(per_chip_n / per_chip_1, 4),
        "loss_1dev": [round(x, 6) for x in losses_1],
        "loss_ndev": [round(x, 6) for x in losses_n],
    }
    # phases of the mesh program's steady-state steps; compile seconds
    # as a compile_stats() DELTA around the n-device measure — the big
    # XLA compile fires in the unprofiled warmup call, so the per-step
    # carve-out (steady-state recompiles) is ~0 by design
    rep = prof_n.report(emit_event=False)
    detail["step_phases_ndev"] = {
        "device_execute_frac": rep.get("device_execute_frac", 0.0),
        "compile_frac": rep.get("compile_frac", 0.0),
        "compile_s": round(
            compile_after["compile_s"] - compile_before["compile_s"], 3),
    }
    return {
        "metric": "train_multichip_tokens_per_sec_per_chip",
        "value": round(per_chip_n, 1),
        "unit": "tokens/s/chip",
        "detail": detail,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--n-devices", type=int, default=None,
                   help="devices to span (default: all visible)")
    p.add_argument("--steps", type=int, default=8)
    args = p.parse_args(argv)
    from ray_tpu._private import compile_cache

    compile_cache.enable()
    import jax

    n = args.n_devices or len(jax.devices())
    print(json.dumps(run(n, steps=args.steps)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
