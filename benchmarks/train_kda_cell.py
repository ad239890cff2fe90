"""The `train_kda` kind of cell: the `train` kind (`train_cell.run`, as it
is) for a model whose layers mix with `ray_tpu/ops/kda.py`'s delta rule,
with the KDA CALL in `correct` beside the loss.

`train_cell` compares the mean cross-entropy of one row at seeded random
weights with the reference's. That number cannot see how the KDA state is
carried: on the v5e at Solar-Open2's published widths the program with its
state rounded to bf16 between chunks stays within `train_cell`'s 3e-4 as
the sound program does (PERF.md section 6, PR 64, has both readings). So
before the `train` cell this kind runs one short `JaxTrainer.fit()` of
`path_fn`, in a gang worker of its own that holds the cell's chips and
lets them go: ONE KDA CALL of the program (`ops/kda.kda` as
`mixers.kda_sublayer` calls it: bf16 q, k, v, float32 g and beta, the
plan the model's `kda_lower_bound` picks) at the cell's shape, [1, heads,
S, head_dim], against the reference module's token-by-token float32
`recurrence`: o and the five gradients for a seeded cotangent, each as
|got - want|_F / |want|_F, on three inputs:

- `layer`: what the first KDA layer the cell holds reads at the weights
  the timed step starts from (the same `init` of the same seed): the
  reference's `kda_inputs` of a seeded row of tokens' embeddings, q, k, v
  rounded to bf16 for both sides; beta in (0, 2). At initialisation the
  gate's g runs from -1e-3 to a few units a token, with rare tails: a bf16
  state and the bounded plan both read what the program reads here
  (PERF.md section 6), so this input holds the call at the seed's own
  weights and the next two hold what a fresh layer's weights do not reach.
- `fast_decay`: the same q, k, v and beta; g down to -60 a step in every
  head's fastest channels (-60 x sigmoid of a seeded logit that rises
  across the channels: a chunk's cumulative decay reaches e^-3840). Kimi
  Linear's softplus gate has no lower bound, so a trained layer may give
  it; the BOUNDED plan, whose row factors assume g >= -5, is not finite
  here.
- `long_memory`: the same q, k, v; the first chunk writes the state under
  the layer's own beta, every later token nearly keeps it (g -2e-5 to
  -2.5e-5 a token, beta 2e-5: a channel that remembers 50,000 tokens, well
  inside the model's 1,048,576 positions). A chunk then changes the state
  by under half a bf16 ulp, so a state rounded to bf16 between chunks
  STANDS STILL where the float32 one decays: the input on which the
  state's precision shows (the delta rule rewrites a fresh layer's state
  every few chunks, so `layer` cannot tell).

`correct` is `train_cell`'s and every input's worst tensor under its limit.
The spans and counters that the metrics read are the `train` fit's alone:
what the call's fit left in this process's aggregate is taken out again.
The parent never imports jax.
"""

from __future__ import annotations

import os

NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
# `long_memory`: the tokens that write the state, one chunk of `ops/kda.py`
_WRITTEN = 64
# An input's limit on its worst tensor, between two readings on the v5e at
# [1, 64, 8192, 128] through this file's `path_errors` (PERF.md section 6,
# PR 64; my chip runs: the program over nine seeds, the controls,
# `tools/kda_chip_check.py --cell`, on two).
# `layer`: the program 4.67e-3 to 4.91e-3 (dv or dg the worst; bf16 rounding
# of four chained matmuls' operands). No control of PR 64 shows here (a
# bf16 state 4.99e-3, the bounded plan 5.50e-3): three times the reading.
# `fast_decay`: the program 4.00e-3 to 4.03e-3; the bounded plan NaN in all
# six tensors.
# `long_memory`: the program 1.050e-2 to 1.065e-2 (dg); a bf16 state 0.118
# (o; dq 0.116, dg 8.7e-2, dbeta 5.7e-2, dk and dv the program's): a factor
# of three from either.
LIMITS = {"layer": 1.5e-2, "fast_decay": 1.5e-2, "long_memory": 3e-2}


def call_errors(kda, recurrence, args, cot):
    """The program's call `kda(q, k, v, g, beta)` (heads first, [1, H, S,
    D]; beta [1, H, S]) against `recurrence` (the reference's: tokens first,
    [S, H, D], float32) on `args` = (q, k, v, g, beta) as the reference lays
    them out, for the cotangent `cot` of o -> {tensor: relative error}.
    Holds jax: call it in the worker."""
    import jax
    import jax.numpy as jnp

    def heads_first(a):
        return jnp.moveaxis(a, 0, 1)[None]

    def tokens_first(a):
        return jnp.moveaxis(a[0], 0, 1).astype(jnp.float32)

    @jax.jit
    def program(*args_and_cot):
        *xs, c = map(heads_first, args_and_cot)
        o, vjp = jax.vjp(kda, *xs)
        return tuple(map(tokens_first, (o,) + vjp(c)))

    @jax.jit
    def plain(*args_and_cot):
        *xs, c = (a.astype(jnp.float32) for a in args_and_cot)
        with jax.default_matmul_precision("highest"):
            o, vjp = jax.vjp(recurrence, *xs)
            return (o,) + vjp(c)

    want = plain(*args, cot)
    got = program(*args, cot)
    return {name: float(jnp.linalg.norm((a - b).ravel())
                        / jnp.linalg.norm(b.ravel()))
            for name, a, b in zip(NAMES, got, want)}


def call_inputs(reference, fields, params, key, seq):
    """-> {input: args} for the three inputs of the docstring, the cotangent
    of o; q, k, v in the parameters' dtype."""
    import jax
    import jax.numpy as jnp

    first = next(i for i in fields["layers"]
                 if i % fields["period"] != fields["full_phase"])
    p = dict(reference.layer_params(params, fields))[first]
    k_tokens, k_cot, k_g, k_fast = jax.random.split(key, 4)
    tokens = jax.random.randint(k_tokens, (seq,), 0, fields["vocab_size"])
    dtype = params["embed"].dtype
    x = params["embed"][tokens].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        q, k, v, g, beta = jax.jit(
            lambda x, p: reference.kda_inputs(x, p, fields)[1])(x, p)
    q, k, v = (a.astype(dtype) for a in (q, k, v))
    fast = -60.0 * jax.nn.sigmoid(jax.random.normal(k_fast, g.shape)
                                  + jnp.linspace(-9.0, 4.0, g.shape[-1]))
    kept = -2e-5 * jax.random.uniform(k_g, g.shape, minval=1.0, maxval=1.25)
    written = jnp.where(jnp.arange(seq)[:, None] < _WRITTEN, beta, 2e-5)
    cot = jax.random.normal(k_cot, v.shape).astype(dtype)
    return {"layer": (q, k, v, g, beta), "fast_decay": (q, k, v, fast, beta),
            "long_memory": (q, k, v, kept, written)}, cot


def within_limits(errors: dict) -> bool:
    return all(errors[f"kda_{name}_err"] <= limit  # a NaN is over
               for name, limit in LIMITS.items())


def path_errors(cfg) -> dict:
    """The compared numbers of the cell `cfg` describes (as `run` builds
    it), on the device this process holds: each input's worst tensor, and
    every tensor's reading under `kda_errors`."""
    import importlib
    from functools import partial

    import jax

    from ray_tpu.ops import kda as kda_op

    program = importlib.import_module(cfg["model_module"])
    reference = importlib.import_module(cfg["reference_module"])
    fields = cfg["model"]
    model = getattr(program, cfg["config_class"])(**fields)
    t, seed = cfg["trainer"], cfg["seed"]
    # the key and the weights as `train_cell.train_fn` makes them
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    params = jax.jit(partial(program.init, model))(key)
    inputs, cot = call_inputs(reference, fields, params,
                              jax.random.fold_in(key, 2), t["seq"])
    del params
    kda = partial(kda_op.kda, g_min=model.kda_lower_bound)

    def worst(by_tensor):  # a NaN among them stays one
        return max(by_tensor.values(), key=lambda v: (v != v, v))

    by_input = {name: call_errors(kda, reference.recurrence, args, cot)
                for name, args in inputs.items()}
    return {**{f"kda_{name}_err": worst(by_input[name]) for name in LIMITS},
            "kda_errors": by_input}


def path_fn(cfg):
    from ray_tpu import train

    errors = path_errors(cfg)
    train.report({"errors": errors, "correct": within_limits(errors)})


def run(ctx: dict) -> dict:
    from benchmarks import train_cell
    from ray_tpu._private import device_profiler
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.train.backend import JaxConfig

    config, chips = ctx["config"], ctx["chips"]
    cfg = {
        "model": ctx["model"], "model_module": config["program"]["module"],
        "config_class": config["program"]["config_class"],
        "reference_module": "benchmarks." + config["reference"],
        "trainer": {**config["trainer"], **ctx["traffic"]},
        "seed": ctx["seed"],
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
    start = device_profiler.snapshot()
    path = JaxTrainer(
        path_fn, train_loop_config=cfg, jax_config=jax_config,
        scaling_config=scaling,
        run_config=RunConfig(name="bench_path", storage_path=os.path.join(
            ctx["out_dir"], "trainer_path")),
    ).fit()
    if path.error is not None:
        raise path.error
    of_path = device_profiler.delta(device_profiler.snapshot(), start)
    result = train_cell.run(ctx)
    rest = device_profiler.delta(device_profiler.snapshot(), of_path)
    result["readings"].update(spans=rest["spans"], counters=rest["counters"])
    result["correct"] = bool(result["correct"] and path.metrics["correct"])
    result["checks"].update(path.metrics["errors"])
    return result
