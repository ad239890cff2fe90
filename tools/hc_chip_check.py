#!/usr/bin/env python3
"""python3 tools/hc_chip_check.py [--seed n] [--model] [--batch b] [--seq s]:
the residual path of four streams (`ray_tpu/models/streams.py`) ON THE CHIP
(any other backend exits 3 before anything is computed), against
`benchmarks/reference_xing.py` in float32 at `highest`, with CONTROLS that
have to miss.

ONE CONNECTION ALONE, at `train-xing4-1chip`'s shapes (X bf16 [4, 4, 2048,
3584], phi [4, 3584, 24], a seeded alpha near 1, b ~ N(0, 1)), through the
comparison that the cell's `correct` holds (`benchmarks/train_hc_cell.py`:
`connection_errors` under `within_limits`, around the sublayer y = h):
- `hc_maps_err`: the maps (H_pre, H_post, H_res) against the reference's, a
  batch row at a time: the largest absolute difference, held to 1e-4
  (float32 maps of the same bf16 X and phi read 1e-6; a bf16 map is rounded
  to 4e-3 of itself);
- `hc_value_err`, `hc_grad_err`: X' and the gradients by X, phi, alpha and b
  for a seeded cotangent, each as |got - want|_F / |want|_F, held to 2e-2
  (X' and dX leave in bf16: 2e-3 is their rounding alone);
- `ms`: a call's busy time on the device's clock (the union of the `XLA Ops`
  events of a short trace) around a branch that costs nothing beside it
  (y = h * g, g [3584]), forward alone and forward + backward, beside
  `bound_ms`, `opcount_xing.hc_bytes` over the chip's 819 GB/s; BOTH FORMS
  side by side: `forms.kernels` (the four Pallas calls of
  `ops/stream_mix.py`, what the program takes on the chip; `calls_ms` has
  each call's own events) and `forms.jnp` (`stream_mix.fused` answered
  False: the plain `jnp` path), each with `passes`: its milliseconds as
  passes over one [tokens, D] bf16 array at the HBM's peak, where
  `hc_bytes` counts 4 n + 4 = 20 and the four calls read and write 14
  forward and 37 forward + backward;
- the controls, each through the same comparison: `one_sinkhorn_iteration`
  (1 for 20), `static_maps` (alpha = 0: the biases alone) and `bf16_maps`
  (the maps of `maps_in_bf16` below), each put where `streams.connect` AND
  `streams.maps` get their maps, `streams.pre_mix` (the first two run
  through the Pallas calls). Each has to MISS a limit; exit 1 if one
  passes, or if the program itself misses.

`--cell`: what the cell's own worker compares before it trains
(`train_hc_cell.path_errors`: the first expert layer's `hc_mlp` of the
weights `--seed` makes, on seeded rows of the embedding), as it is and with
each control in the program's place: `correct` has to come out false under
every control.

`--model`: the whole model at the cell's configuration file (published
widths, the share, the depth the file gives) on seeded weights: the
program's loss on `reference_rows` rows of one seeded batch against the
reference's, as `benchmarks/train_cell.py` compares them (|got - want| /
want, held to its 3e-4), and the same three controls patched into the
PROGRAM's side. A control that passes there says the cell's comparison is
too loose for the mechanism (at random weights the mean cross-entropy of
8,192 tokens hardly moves with the residual path: all three pass it,
PERF.md section 6, PR 61): it is reported (`model_controls_missed`) and
does not fail the tool. What holds the mechanism at the published widths
is finer: the LOGITS of the batch's first row against the reference's, the
median token's |got - want|_2 / |want|_2, held to 6e-2 (the program reads
3.5e-2, its bf16 rounding through twenty sublayers, one Sinkhorn iteration
0.114, alpha = 0 0.43; `model_controls_missed_by_logits`), the comparisons
of the connection above, and tests/test_xing_reference.py at the test size.
Exit 1 if the program misses the loss's or the logits' limit, or if
`one_sinkhorn_iteration` or `static_maps` passes the logits' (a bf16 map is
the size of the bf16 streams' own rounding there too: the connection's maps
hold that one).

`--step LAYERS`: and nothing else: the cell's model cut to LAYERS layers (2:
one dense, one expert layer and the MTP block; 9: the cell's depth), four
AdamW steps of the jitted train step on a seeded batch under a watchdog: a
step that has not come back `--watchdog` seconds (300) after the process
started ends the process with its stack printed (the v5e HUNG in a step
that held a Pallas call stating 37 MiB or more of VMEM beside a share's
routed block: PERF.md section 6, PR 62); prints each step's loss and
milliseconds. Run it after any change to what the path's calls state.

Writes chiprun_out/hc_chip_check.json.
"""
import argparse
import contextlib
import dataclasses
import glob
import json
import os
import sys
import tempfile
from functools import partial

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import opcount_xing, peaks, reduce_trace  # noqa: E402
from benchmarks import reference_xing as ref  # noqa: E402
from benchmarks import train_hc_cell  # noqa: E402
from benchmarks.train_cell import LOSS_TOLERANCE  # noqa: E402
from ray_tpu.models import mla_moe, streams  # noqa: E402
from ray_tpu.ops import stream_mix  # noqa: E402

CONFIG = os.path.join(ROOT, "benchmarks", "configs",
                      "xing4.0-29b-a4b-train-1chip.json")
LOGITS_LIMIT = 6e-2
CONTROLS = ("one_sinkhorn_iteration", "static_maps", "bf16_maps")


def cell_config():
    """The cell's `MlaMoeConfig` from its configuration file, and the fields
    as the reference takes them."""
    with open(CONFIG) as f:
        config = json.load(f)
    program = config["program"]
    fields = {k: config[v] for k, v in program["fields_from"].items()}
    fields.update(program["fields"])
    return mla_moe.MlaMoeConfig(**fields), fields, config


def maps_in_bf16(X, p, config):
    """The maps with everything after the float32 sums over the channels
    in bfloat16, the nearest precision below the one `streams` states."""
    c, bf16 = config, jnp.bfloat16
    n = X.shape[0]
    square = X.astype(jnp.float32)
    inv_rms = jax.lax.rsqrt(
        jnp.mean(square * square, axis=(0, 3)) + c.norm_eps)
    a = (jnp.einsum("nbsd,ndm->mbs", X, p["phi"],
                    preferred_element_type=jnp.float32) * inv_rms).astype(bf16)
    alpha, bias = p["alpha"].astype(bf16), p["b"].astype(bf16)[:, None, None]
    pre = jax.nn.sigmoid(alpha[0] * a[:n] + bias[:n])
    post = 2 * jax.nn.sigmoid(alpha[1] * a[n:2 * n] + bias[n:2 * n])
    res = jnp.exp(jnp.clip(alpha[2] * a[2 * n:] + bias[2 * n:],
                           c.h_res_clamp_min, c.h_res_clamp_max))
    return pre, post, streams.sinkhorn(
        res.reshape((n, n) + a.shape[1:]), c.hc_sinkhorn_iters,
        jnp.asarray(c.hc_eps, bf16))


def pre_mix_in_bf16(X, p, config, mesh=None):
    """`streams.pre_mix` on `maps_in_bf16`, in the `jnp` path's form."""
    mapped = maps_in_bf16(X, p, config)
    h = sum(mapped[0][i][..., None] * X[i].astype(jnp.float32)
            for i in range(X.shape[0])).astype(X.dtype)
    return h, (X, mapped)


@contextlib.contextmanager
def controlled(name):
    """Control `name` in the place of the PROGRAM's `streams.pre_mix`, where
    `streams.connect` and `streams.maps` both get their maps; None: the
    program as it is."""
    pre_mix = streams.pre_mix
    streams.pre_mix = {
        None: pre_mix,
        "one_sinkhorn_iteration": lambda X, p, c, *where: pre_mix(
            X, p, dataclasses.replace(c, hc_sinkhorn_iters=1), *where),
        "static_maps": lambda X, p, c, *where: pre_mix(
            X, dict(p, alpha=0 * p["alpha"]), c, *where),
        "bf16_maps": pre_mix_in_bf16}[name]
    try:
        yield
    finally:
        streams.pre_mix = pre_mix


@contextlib.contextmanager
def jnp_path():
    """`stream_mix.fused` answered False: the plain `jnp` path on the
    chip."""
    fused = stream_mix.fused
    stream_mix.fused = lambda X, mesh=None: False
    try:
        yield
    finally:
        stream_mix.fused = fused


def frob(got, want):
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def median_row(got, want):
    """The MEDIAN over rows (tokens) of |got - want|_2 / |want|_2: a token
    whose expert choice flips on the program's bf16 rounding is off by tens
    of percent with any weights (9% of the whole array's norm at the
    published widths, my chip run, PR 61), and the median does not see it."""
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    return float(np.median(np.linalg.norm(got - want, axis=-1)
                           / np.linalg.norm(want, axis=-1)))


# a Pallas call of the path, by the scope in its event's name
CALLS = {"hc_calls": {
    "op": r"^%[\w.\-]*hc\.[\w.\-]* = .* custom-call\(.*tpu_custom_call"}}


def busy_ms(fn, args, calls=4):
    """-> (a call's busy device milliseconds: the union of the `XLA Ops`
    events of a trace over `calls` calls; {the path's Pallas calls by their
    output: ms a call})."""
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as where:
        jax.profiler.start_trace(where)
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            where, "plugins", "profile", "*", "*.xplane.pb"))
        reduced = reduce_trace.reduce_xplane(path, CALLS)
    top = reduced["device_ops"][:8]
    return 1e3 * reduced["busy_s"] / calls, {
        "hc_calls_ms": 1e3 * (reduced["queries"]["hc_calls"]
                              or {"total_s": 0.0})["total_s"] / calls,
        "top_ops_ms": {k: 1e3 * v / calls for k, v in top}}


def connection_check(seed, b=4, s=2048):
    cfg, fields, _ = cell_config()
    n, d = cfg.hc_mult, cfg.d_model
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    k_p, k_x, k_g, k_c, k_a = jax.random.split(key, 5)
    p = streams.init_connection(cfg, k_p)
    p["alpha"] = 1.0 + 0.25 * jax.random.uniform(k_a, (3,), minval=-1.0)
    X = jax.random.normal(k_x, (n, b, s, d), jnp.float32).astype(cfg.dtype)
    g = (1.0 + 0.1 * jax.random.normal(k_g, (d,))).astype(cfg.dtype)
    cot = jax.random.normal(k_c, X.shape, jnp.float32).astype(cfg.dtype)

    def compare(name):
        with controlled(name):
            out = train_hc_cell.connection_errors(cfg, fields, ref, p, X, cot)
        out["within_limits"] = train_hc_cell.within_limits(out)
        return out

    def program(X, p, g):
        return streams.connect(X, p, lambda h: (h * g, None), cfg)[0]

    result = {"program": compare(None),
              "controls": {name: compare(name) for name in CONTROLS},
              "limits": {"hc_maps_err": train_hc_cell.MAPS_LIMIT,
                         "hc_value_err": train_hc_cell.VALUE_LIMIT,
                         "hc_grad_err": train_hc_cell.VALUE_LIMIT}}
    nbytes = opcount_xing.hc_bytes(fields, b * s)
    peak = peaks.peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    pass_ms = 1e3 * X[0].nbytes / peak

    def timed():
        # fresh functions: a jitted one would keep the other form's trace
        fwd, fwd_ops = busy_ms(jax.jit(lambda X, p, g: program(X, p, g)),
                               (X, p, g))
        both, both_ops = busy_ms(jax.jit(
            lambda X, p, g: jax.vjp(program, X, p, g)[1](cot)), (X, p, g))
        return {"fwd_ms": fwd, "fwd_bwd_ms": both,
                "passes": {"fwd": fwd / pass_ms, "fwd_bwd": both / pass_ms},
                "fwd_ops": fwd_ops, "fwd_bwd_ops": both_ops}

    forms = {"kernels" if stream_mix.fused(X) else "jnp": timed()}
    if "kernels" in forms:
        with jnp_path():
            forms["jnp"] = timed()
    first = next(iter(forms.values()))
    result.update(
        fwd_ms=first["fwd_ms"], fwd_bwd_ms=first["fwd_bwd_ms"], forms=forms,
        bound_ms=1e3 * nbytes / peak, hc_bytes=nbytes, pass_ms=pass_ms,
        passes={"hc_bytes": nbytes / X[0].nbytes, "kernels_fwd": 3 * n + 2,
                "kernels_fwd_bwd": 8 * n + 5})
    return result


def cell_check(seed):
    _, fields, config = cell_config()
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "pretrain-2k.json")) as f:
        traffic = json.load(f)
    cfg = {"model": fields, "model_module": config["program"]["module"],
           "config_class": config["program"]["config_class"],
           "reference_module": "benchmarks." + config["reference"],
           "trainer": {**config["trainer"], **traffic}, "seed": seed}
    out = {}
    for name in (None,) + CONTROLS:
        with controlled(name):
            errors = train_hc_cell.path_errors(cfg)
        out[name or "program"] = dict(
            errors, correct=train_hc_cell.within_limits(errors))
    return out


def model_check(seed):
    cfg, fields, config = cell_config()
    t = config["trainer"]
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    params = jax.jit(partial(mla_moe.init, cfg))(key)
    rows, seq = t["reference_rows"], cfg.max_seq_len
    toks = jax.random.randint(jax.random.fold_in(key, 1), (rows, seq + 1), 0,
                              cfg.vocab_size)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    want = ref.loss(params, batch["inputs"], batch["targets"], fields)
    want_logits = ref.logits(params, batch["inputs"][0], fields)
    out = {"loss_reference": want, "tolerance": LOSS_TOLERANCE,
           "logits_limit": LOGITS_LIMIT}

    def both(c, p, batch):
        return (mla_moe.loss_fn(p, batch, c),
                mla_moe.forward(p, batch["inputs"][:1], c)[0])

    for name in (None,) + CONTROLS:
        with controlled(name):
            got, logits = jax.jit(partial(both, cfg))(params, batch)
        got = float(got)
        out[name or "program"] = {
            "loss": got, "rel_err": abs(got - want) / abs(want),
            "logits": median_row(logits, want_logits),
            "logits_whole": frob(logits, want_logits)}
    out["model_controls_missed"] = {
        name: out[name]["rel_err"] > LOSS_TOLERANCE for name in CONTROLS}
    out["model_controls_missed_by_logits"] = {
        name: out[name]["logits"] > LOGITS_LIMIT for name in CONTROLS}
    return out


def step_check(layers, watchdog):
    """Four train steps of the cell's model at `layers` layers, the path's
    Pallas calls in it; the watchdog ends a hung process."""
    import faulthandler
    import time

    import optax

    faulthandler.dump_traceback_later(watchdog, exit=True)
    _, fields, _ = cell_config()
    cfg = mla_moe.MlaMoeConfig(**dict(fields, n_layers=layers))
    params = jax.jit(partial(mla_moe.init, cfg))(jax.random.PRNGKey(0))
    opt = optax.adamw(1e-4, weight_decay=0.0)
    state = {"params": params, "opt_state": jax.jit(opt.init)(params)}

    def step(state, batch):
        loss, grads = jax.value_and_grad(partial(
            mla_moe.loss_fn, config=cfg))(state["params"], batch)
        updates, new = opt.update(grads, state["opt_state"], state["params"])
        return {"params": optax.apply_updates(state["params"], updates),
                "opt_state": new}, loss

    toks = jax.random.randint(jax.random.PRNGKey(1), (4, cfg.max_seq_len + 1),
                              0, cfg.vocab_size)
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    compiled = jax.jit(step, donate_argnums=(0,)).lower(state, batch).compile()
    print("compiled", flush=True)
    for i in range(4):
        start = time.time()
        state, loss = compiled(state, batch)
        print(f"step {i} loss {float(loss):.4f} "
              f"{1e3 * (time.time() - start):.1f} ms", flush=True)
    faulthandler.cancel_dump_traceback_later()
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2610000001)
    ap.add_argument("--cell", action="store_true")
    ap.add_argument("--model", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--step", type=int, default=0, metavar="LAYERS")
    ap.add_argument("--watchdog", type=int, default=300)
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"needs the chip: the backend is {device.platform}",
              file=sys.stderr)
        return 3
    if args.step:
        return step_check(args.step, args.watchdog)
    result = {"seed": args.seed, "device": device.device_kind,
              "connection": connection_check(args.seed, args.batch,
                                             args.seq)}
    if args.cell:
        result["cell"] = cell_check(args.seed)
    if args.model:
        result["model"] = model_check(args.seed)
    where = os.path.join(ROOT, "chiprun_out")
    os.makedirs(where, exist_ok=True)
    with open(os.path.join(where, "hc_chip_check.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    conn = result["connection"]
    ok = conn["program"]["within_limits"] and not any(
        c["within_limits"] for c in conn["controls"].values())
    if args.cell:
        cell = result["cell"]
        ok = ok and cell["program"]["correct"] and not any(
            cell[name]["correct"] for name in CONTROLS)
    if args.model:
        model = result["model"]
        ok = ok and model["program"]["rel_err"] <= LOSS_TOLERANCE \
            and model["program"]["logits"] <= LOGITS_LIMIT \
            and model["model_controls_missed_by_logits"][
                "one_sinkhorn_iteration"] \
            and model["model_controls_missed_by_logits"]["static_maps"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
