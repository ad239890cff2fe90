"""The `train` kind of cell: `JaxTrainer(...).fit()` in mesh-native mode
with the benchmark's own `train_fn`, as `chip_smoke.train_phase` drives it.

The parent (this module's `run`) never imports jax. `train_fn` runs in the
one gang worker that owns the cell's chips: it makes the weights on the
device from the seed, stages a cycle of seeded batches, checks the loss
against `reference.py`, warms up, measures, and (with --trace 1) traces a
few steps of the window and reduces the trace there.
"""

from __future__ import annotations

import os
import time

# |program loss - float32 reference loss| over the reference loss. The
# program multiplies in bf16 (8 bits of mantissa) with float32
# accumulation: single logits move by ~1e-2, but the mean cross-entropy of
# 2,048 tokens averages that out. Measured on the v5e at 10 layers of the
# published widths: 0.8e-5 to 2.1e-5 over four seeds (PERF.md, PR 24). The
# bound is ~15x that; an 8-bit float (3 bits of mantissa, 32x the rounding
# step) would break it.
LOSS_TOLERANCE = 3e-4


def train_fn(cfg):
    t_enter = time.time()
    import glob
    import importlib
    from functools import partial

    import jax
    import jax.numpy as jnp
    import optax

    from benchmarks import loadgen, reduce_trace
    from ray_tpu import train
    from ray_tpu._private.device_profiler import (
        compile_stats,
        hbm_stats,
        install_compile_listener,
    )
    from ray_tpu.parallel.sharding import LogicalAxisRules

    install_compile_listener()
    # the model module's part of the contract: its config class (named
    # in the configuration), `init`, `param_logical_axes`, `loss_fn`
    llama = importlib.import_module(cfg["model_module"])
    reference = importlib.import_module(cfg["reference_module"])
    mesh = train.get_mesh()
    devices = jax.devices()
    chips = len(devices)
    model_fields = cfg["model"]
    model = getattr(llama, cfg["config_class"])(**model_fields)
    t = cfg["trainer"]
    seed = cfg["seed"]
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    rules = LogicalAxisRules()
    opt = optax.adamw(t["learning_rate"], weight_decay=t["weight_decay"])
    state, shardings = train.init_train_state(
        partial(llama.init, model), opt, llama.param_logical_axes(model),
        mesh, key, rules)
    bs = train.batch_sharding(mesh, rules)
    loss_fn = partial(llama.loss_fn, config=model, mesh=mesh, rules=rules)
    step = train.make_train_step(
        loss_fn, opt, shardings, batch_sharding={"inputs": bs, "targets": bs})
    batch, seq = t["per_chip_batch"] * chips, t["seq"]
    # the whole cycle of batches in one jitted call, staged on the device
    toks = jax.jit(
        lambda k: jax.random.randint(
            k, (t["batches_in_cycle"], batch, seq + 1), 0, model.vocab_size),
        out_shardings=jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, *bs.spec)))(
                jax.random.fold_in(key, 1))
    cycle = [jax.device_put({"inputs": toks[i, :, :-1],
                             "targets": toks[i, :, 1:]}, bs)
             for i in range(t["batches_in_cycle"])]

    # correct, part 1: the program's loss on the first rows of one seeded
    # batch against the plain float32 reference at the same widths
    rows = t["reference_rows"]
    mask = (jnp.arange(batch) < rows)[:, None] * jnp.ones((1, seq))
    got = float(jax.jit(loss_fn)(
        state.params, dict(cycle[0], mask=jax.device_put(
            mask.astype(jnp.float32), bs))))
    want = reference.loss(state.params, cycle[0]["inputs"][:rows],
                          cycle[0]["targets"][:rows], model_fields)
    loss_rel_err = abs(got - want) / abs(want)

    # warm-up on one repeated batch: compiles the step; the loss must fall
    warm = []
    for _ in range(t["warmup_steps"]):
        state, m = step(state, cycle[0])
        warm.append(float(m["loss"]))
    compiles_before = compile_stats()["compiles"]

    trace_dir = cfg["trace_dir"]
    t_window_wall = time.time()
    t0 = time.perf_counter()
    stamps, losses, tracing = [], [], False
    i = 0
    while True:
        if trace_dir and i == t["trace_after_steps"]:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
        state, m = step(state, cycle[i % len(cycle)])
        losses.append(float(m["loss"]))  # the host transfer is the fence
        now = time.perf_counter()
        stamps.append(now)
        i += 1
        if tracing and i == t["trace_after_steps"] + t["trace_steps"]:
            jax.profiler.stop_trace()
            tracing = False
        if now - t0 >= cfg["seconds"] and not tracing:
            break
    compiles_in_window = compile_stats()["compiles"] - compiles_before
    hbm = hbm_stats(export=False)
    finite = all(x == x and abs(x) != float("inf") for x in warm + losses)

    traced = None
    if trace_dir:
        paths = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        traced = reduce_trace.reduce_xplane(paths[0], cfg["trace_queries"]) \
            if paths else None

    host = loadgen.interval_stats(stamps, t0, batch * seq, chips)
    host.update({
        "trainer_start_s": t_enter - cfg["t_fit_wall"],
        "compiles_in_window": compiles_in_window,
        "compile_s": compile_stats()["compile_s"],
        "loss_rel_err": loss_rel_err, "loss_program": got,
        "loss_reference": want, "warmup_losses": warm,
        "first_loss": losses[0], "last_loss": losses[-1],
    })
    train.report({
        "t_window_wall": t_window_wall,
        "host": host,
        "trace": traced,
        "correct": bool(finite and warm[-1] < warm[0]
                        and loss_rel_err <= LOSS_TOLERANCE),
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": chips,
            "memory_peak_bytes": max(
                (s.get("peak_bytes_in_use", 0) for s in hbm.values()),
                default=0)},
    })


def run(ctx: dict) -> dict:
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.train.backend import JaxConfig

    config, chips = ctx["config"], ctx["chips"]
    cfg = {
        "model": ctx["model"], "model_module": config["program"]["module"],
        "config_class": config["program"]["config_class"],
        "reference_module": "benchmarks." + config["reference"],
        "trainer": {**config["trainer"], **ctx["traffic"]},
        "seed": ctx["seed"], "seconds": ctx["seconds"],
        "trace_dir": ctx["trace_dir"], "trace_queries": ctx["trace_queries"],
        "t_fit_wall": time.time(),
    }
    if ctx["rehearse"]:
        jax_config = JaxConfig(
            distributed=False, platform="cpu", env_vars={
                "XLA_FLAGS":
                    f"--xla_force_host_platform_device_count={chips}"},
            mesh_config=MeshConfig(**config["mesh"]))
        scaling = ScalingConfig(num_workers=1)
    else:
        jax_config = JaxConfig(mesh_config=MeshConfig(**config["mesh"]))
        scaling = ScalingConfig(num_workers=1, use_tpu=True,
                                resources_per_worker={"TPU": chips})
    result = JaxTrainer(
        train_fn, train_loop_config=cfg, jax_config=jax_config,
        scaling_config=scaling,
        run_config=RunConfig(name="bench", storage_path=os.path.join(
            ctx["out_dir"], "trainer")),
    ).fit()
    if result.error is not None:
        raise result.error
    m = result.metrics
    steps = m["host"]["steps"]
    return {
        "t_window_wall": m["t_window_wall"],
        "correct": m["correct"],
        "attempted": steps, "failed": 0,
        "readings": {"host": m["host"], "trace": m["trace"]},
        "checks": {k: m["host"][k] for k in (
            "loss_rel_err", "loss_program", "loss_reference",
            "warmup_losses", "first_loss", "last_loss")},
        "device": m["device"],
    }
