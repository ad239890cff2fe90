"""The `train_hc` kind of cell: the `train` kind (`train_cell.run`, as it
is) for a model on `ray_tpu/models/streams.py`'s residual path of several
streams, with the PATH in `correct` beside the loss.

`train_cell` compares the mean cross-entropy of one batch at seeded random
weights with the reference's. That number hardly moves with what a residual
path does to single tokens: on the v5e at Xing4.0's published widths the
program with ONE Sinkhorn iteration for twenty, with alpha = 0 (static
maps) and with the maps in bf16 each stays within its 3e-4 (PERF.md section
6, PR 61). So before the `train` cell this kind runs one short
`JaxTrainer.fit()` of `path_fn`, in a gang worker of its own that holds the
cell's chips and lets them go: ONE CONNECTION of the program
(`streams.maps`, `streams.connect`) on the weights the timed step starts
from (the same `init` of the same seed; the first expert layer's `hc_mlp`)
against the reference module's (`hc_maps`, `connection`; float32 at
`highest`), a row of S tokens at a time, around the sublayer y = h, for a
seeded cotangent. A token's n streams are n seeded rows of the embedding.
Compared, each under a limit of its own:

- `hc_maps_err`: the largest absolute difference of H_pre, H_post and
  H_res over every token;
- `hc_value_err`: |X' - X'_ref|_F / |X'_ref|_F;
- `hc_grad_err`: the largest of the same norm's ratio over the gradients by
  X, phi, alpha and b.

`correct` is `train_cell`'s and all three. The spans and counters that the
metrics read are the `train` fit's alone: what the path's fit left in this
process's aggregate is taken out again. The parent never imports jax.
"""

from __future__ import annotations

import os

# The two readings of each limit, on the v5e at the published widths
# (PERF.md section 6, PR 61; my chip runs). The maps: float32 maps of the
# same bf16 X and phi read 9.5e-7 to 1.55e-6 over ten seeds; the nearest
# precision below, the maps in bf16, reads 6.4e-3 to 7.0e-3 (a bf16 map is
# rounded to 4e-3 of itself), one Sinkhorn iteration 0.41 to 0.50, alpha = 0
# 1.25 to 1.49.
MAPS_LIMIT = 1e-4
# X' and dX leave in bf16, 2e-3 their rounding alone: the program reads
# 1.66e-3 (X') and 2.5e-3 to 4.1e-3 (the gradients); one Sinkhorn iteration
# reads 6.4e-2 in X' and 0.39 in the gradients, alpha = 0 0.37 and 1.0. bf16
# maps read 2.4e-3 and 6.4e-3 here: `MAPS_LIMIT` is the one that catches
# them.
VALUE_LIMIT = 2e-2


def connection_errors(model, fields, reference, p, X, cot):
    """The program's connection `p` on X [n, rows, S, D] (`model`: its config;
    cotangent `cot`, shaped like X) against the reference's under `fields`
    -> the three compared numbers. Holds jax: call it in the worker."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import streams

    def as_f32(tree):
        return jax.tree.map(lambda a: a.astype(jnp.float32), tree)

    def ident(h):
        return h, None

    @jax.jit
    def program(X, p, cot):
        out, vjp = jax.vjp(
            lambda X, p: streams.connect(X, p, ident, model)[0], X, p)
        return streams.maps(X, p, model), out, vjp(cot)

    @jax.jit
    def plain(x, p, c):
        """One row, x [n, S, D]: the reference's H_pre, H_post, H_res, X',
        dX and the parameters' gradients, the streams leading as the
        program has them."""
        def tokens_first(a):
            return jnp.moveaxis(a, 0, 1)

        with jax.default_matmul_precision("highest"):
            out, vjp = jax.vjp(
                lambda x, p: reference.connection(
                    tokens_first(x), p, ident, fields)[0], x, p)
            pre, post, res = reference.hc_maps(tokens_first(x), p, fields)
            return (pre.T, post.T, jnp.moveaxis(res, 0, 2),
                    jnp.moveaxis(out, 1, 0)) + vjp(tokens_first(c))

    rows = [plain(*as_f32((X[:, r], p, cot[:, r])))
            for r in range(X.shape[1])]

    def beside(i, axis):  # the rows' i-th results, `rows` before S
        return jnp.stack([row[i] for row in rows], axis=axis)

    want_maps = (beside(0, 1), beside(1, 1), beside(2, 2))
    want_out, want_dX = beside(3, 1), beside(4, 1)
    want_dp = jax.tree.map(lambda *a: sum(a), *[row[5] for row in rows])
    maps, out, (dX, dp) = as_f32(program(X, p, cot))

    def frob(got, want):
        return (jnp.linalg.norm((got - want).ravel())
                / jnp.linalg.norm(want.ravel()))

    def worst(values):  # a NaN among them stays one
        return float(jnp.max(jnp.stack(values)))

    return {
        "hc_maps_err": worst([jnp.abs(a - b).max()
                              for a, b in zip(maps, want_maps)]),
        "hc_value_err": float(frob(out, want_out)),
        "hc_grad_err": worst([frob(dX, want_dX)] + [
            frob(dp[k], want_dp[k]) for k in ("phi", "alpha", "b")]),
    }


def within_limits(errors: dict) -> bool:
    return bool(errors["hc_maps_err"] <= MAPS_LIMIT
                and errors["hc_value_err"] <= VALUE_LIMIT
                and errors["hc_grad_err"] <= VALUE_LIMIT)


def path_errors(cfg) -> dict:
    """The compared numbers of the cell `cfg` describes (as `run` builds
    it), on the device this process holds."""
    import importlib
    from functools import partial

    import jax

    program = importlib.import_module(cfg["model_module"])
    reference = importlib.import_module(cfg["reference_module"])
    model = getattr(program, cfg["config_class"])(**cfg["model"])
    t, seed = cfg["trainer"], cfg["seed"]
    # the key and the weights as `train_cell.train_fn` makes them
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    params = jax.jit(partial(program.init, model))(key)
    p = jax.tree.map(lambda a: a[0], params["layers"]["hc_mlp"])
    k_tokens, k_cot = jax.random.split(jax.random.fold_in(key, 2))
    tokens = jax.random.randint(
        k_tokens, (model.hc_mult, t["reference_rows"], t["seq"]), 0,
        model.vocab_size)
    X = params["embed"][tokens]
    cot = jax.random.normal(k_cot, X.shape, X.dtype)
    return connection_errors(model, cfg["model"], reference, p, X, cot)


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
