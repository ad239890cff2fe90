#!/usr/bin/env python3
"""python3 tools/kda_chip_check.py [--seed n] [--shapes ling solar]
[--kinds KIND ...] | --cell [--seed n ...]:
`ops/kda.py`'s three Pallas kernels alone at the Ling cell's shape, `[4, 32,
2048, 128]`, under the BOUNDED plan (`g_min` -5, the top level of the output)
and, under `any_decay`, the ANY-DECAY plan (`g_min` None), and the same two
at the Solar cell's, `[1, 64, 8192, 128]` (under `solar`), ON THE CHIP:
any other backend exits 3 before anything is computed, and every call here
passes `use_pallas=True`, so no number of this tool ever comes from the
`jnp` form. o and the five gradients against the token-by-token float32
recurrence (relative error in the Frobenius norm, a tensor at a time) on
three kinds of input, the same with a BF16 STATE (the kernels themselves,
the state a chunk hands the next rounded to bf16: the control, which has to
FAIL the bound) or, on `aligned_keys`, with the solve as the PRODUCT OF THE
CHUNK'S POWERS (the control there), and the calls' times on the host's clock.

THE INPUTS. `mixed`: every channel its own decay, from "forgets at once"
(g ~ -5) to "keeps for thousands of tokens" (g ~ -6e-4), beta ~ 0.5: what a
layer sees at initialisation. `long_memory`: the first chunk writes the
state as `mixed` does and every later token nearly keeps it (g -2e-5 to
-2.5e-5 a token, beta 2e-5): a chunk changes the state by at most 1.6e-3 of
itself, under half a bf16 ulp (2^-9 to 2^-8 of the value), so a state
rounded between chunks STANDS STILL where the float32 one has decayed by
~4.5% at the sequence's end. `aligned_keys`: every key within ~25 degrees of
its head's one direction, beta ~0.98, g -5e-4 to -5e-2: past where ten
training steps take a layer (PERF.md section 6, PR 39). A is then near the
all-ones strictly lower matrix, whose powers reach 4.5e17 before they
vanish: (I - A)(I + A^2) ... (I + A^32) keeps no digit there or overflows
(`product_solve()`, the control), `ops/kda._solve` does (blocks of 8 by
their powers, then blocks in pairs). The same keys make the problem itself
touchier (a bf16 score of ~1 is off by 2^-9 and the solve carries that
through 63 rows), so this input has a bound of its own, `ALIGNED_BOUND`.
The any-decay plan takes two more. `fast_decay`: `mixed` with g down to -60
a step in the fastest channels (a chunk's cumulative decay reaches e^-3840:
any positive exponent or division by it is inf or NaN); the control is the
BOUNDED plan on the same input, which has to fail. `negative_eigenvalues`:
`aligned_keys`' keys with beta 1.9 to 1.999 (I - beta k k^T has an
eigenvalue near -1 along k): the control is the product of powers again.

THE BOUND, 7e-3 a tensor (PERF.md section 6, PR 39, has the readings; 2e-2
for `long_memory` at S 8,192, `LONG_MEMORY_BOUND_8K`). The
chunked form rounds each matmul's operands to bf16 (2^-9 a value) with
float32 accumulation, through a chain of four matmuls a chunk (the scores,
T, U / W, U~, o), and the backward kernels round the cotangents the same
way: 3e-3 to 5e-3 a tensor. On `mixed` a bf16 state reads what the float32
state reads (the delta rule rewrites the state every few chunks and the
state enters every matmul as a bf16 operand anyway), so `mixed` alone
cannot tell them apart; on `long_memory` it misses the bound several times
over in o. Exit 1 if a tensor of the float32 state misses the bound on
any input, the bf16 state passes everywhere on `long_memory`, the
product of powers passes everywhere on `aligned_keys`, or o or a gradient
of `mixed` differs in any bit from the same kernels with every row's solve
its own 64 x 64 products (`unpacked_solve()`: the MXU has to add the exact
zeros of a lane-packed pair's product exactly).

`ms`: `fwd` the forward kernel, `fwd_xla` the chunked form in XLA,
`fwd_and_bwd` the gradient of a LINEAR function of o: the forward is dead
code there, so it is the two backward kernels, on the host's clock; and the
three kernels APART on the device's, from a short trace of value and
gradient: `fwd_kernel_ms`, `states_ms` (the backward pass's walk forwards:
the forward's solve again) and `bwd_ms` (its walk backwards), told apart by
how many outputs a Pallas event has. Writes chiprun_out/kda_chip_check.json.
`--kinds` keeps those inputs only (a second seed of one input in minutes).

`--cell`: and nothing else: what `train-solar2-1chip`'s `correct` compares,
through the cell's own functions on the cell's own weights, for each
`--seed`: `benchmarks/train_kda_cell.path_errors` (one KDA call at [1, 64,
8192, 128] against `reference_solar2.recurrence`, on the layer's own input
and on `long_memory`) and `train_cell`'s loss comparison (the whole model's
loss on one row against `reference_solar2.loss`), each with the PROGRAM and
with the two controls in its place: `bf16_state` and `bounded_plan`. Exit 1
unless the program is within the cell's limits and each control outside
them (by `path_errors`; what the loss says of a control is printed, and is
why the cell has a kind of its own). Writes chiprun_out/kda_cell_check.json.
"""
import argparse
import contextlib
import glob
import json
import os
import re
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import kda as K  # noqa: E402

BOUND = 7e-3
NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")
ALIGNED_BOUND = 3e-2
# kind -> (its bound, its control)
KINDS = {"mixed": (BOUND, "bf16_state"), "long_memory": (BOUND, "bf16_state"),
         "aligned_keys": (ALIGNED_BOUND, "product_solve")}
# `long_memory` past S 2,048: the float32 state's own reading grows with the
# chunks a state is carried over (o 4.7e-3 at 32 chunks, 8.7e-3 at 128), under
# BOTH plans alike to three digits, and so does the bf16 control's (o 2.9e-2
# and 0.117). At [1, 64, 8192, 128] over the seeds 39, 40 and 41 (my chip runs,
# PR 64; the first seed's run came before the bound, the others after): the
# kernels read at most o 8.8e-3, dq 8.5e-3, dk 8.8e-3, dv 9.5e-3, dg 1.05e-2,
# dbeta 7.8e-3; the bf16 state at least o 0.117, dq 0.116, dg 8.6e-2, dbeta
# 4.0e-2, and in dk and dv what the kernels read (the control rounds the
# FORWARD state alone; the writing chunk's dk and dv come from the backward
# walk's state, which it leaves in float32): the bound lies between the two
# in the four tensors the control moves, a factor of two from either
LONG_MEMORY_BOUND_8K = 2e-2
# the inputs only the any-decay plan takes
ANY_KINDS = {**KINDS, "fast_decay": (BOUND, "bounded_plan"),
             "negative_eigenvalues": (ALIGNED_BOUND, "product_solve")}
SHAPES = {"ling": (4, 32, 2048, 128), "solar": (1, 64, 8192, 128)}
# the controls that have to fail, by input
MUST_FAIL = {"long_memory": "bf16_state", "aligned_keys": "product_solve",
             "fast_decay": "bounded_plan",
             "negative_eigenvalues": "product_solve"}


def inputs(kind, key, b=4, h=32, s=2048, d=128, dtype=jnp.bfloat16):
    """-> ((q, k, v in `dtype`, g, beta float32), the cotangent of o)."""
    ks = jax.random.split(key, 7)
    l2 = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = l2(jax.nn.silu(jax.random.normal(ks[0], (b, h, s, d)))) * d ** -0.5
    k = l2(jax.nn.silu(jax.random.normal(ks[1], (b, h, s, d))))
    v = jax.nn.silu(jax.random.normal(ks[2], (b, h, s, d)))
    logit = jax.random.normal(ks[3], (b, h, s, d)) \
        + jnp.linspace(-9.0, 4.0, d)
    g = -5.0 * jax.nn.sigmoid(logit)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, s)))
    if kind == "long_memory":
        g = -2e-5 * jax.random.uniform(ks[6], g.shape, minval=1.0, maxval=1.25)
        beta = jnp.where(jnp.arange(s) < K.CHUNK, beta, 2e-5)
    if kind == "fast_decay":
        g = -60.0 * jax.nn.sigmoid(logit)
    if kind in ("aligned_keys", "negative_eigenvalues"):
        k = l2(jax.random.normal(ks[6], (b, h, 1, d))
               + 0.4 * jax.random.normal(ks[1], (b, h, s, d)))
        g = -5e-2 * jax.random.uniform(ks[3], g.shape, minval=0.01)
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, s)) + 4.0)
    if kind == "negative_eigenvalues":
        beta = jax.random.uniform(ks[4], (b, h, s), minval=1.9, maxval=1.999)
    w = jax.random.normal(ks[5], (b, h, s, d))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), w


@contextlib.contextmanager
def _kernels_with(name, value):
    """Inside, `ops/kda.<name>` is `value`. The jit caches are dropped on
    the way in and out: the kernels' traces are cached by shape."""
    kept = getattr(K, name)
    setattr(K, name, value)
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(K, name, kept)
        jax.clear_caches()


def bf16_state():
    """Inside, the kernels carry their state in bf16: `_chunk_forward` (a
    chunk's step in the forward kernel and in the backward pass's first
    walk) hands on a rounded state."""
    step = K._chunk_forward

    def rounded(*args):
        o, new, m, state = step(*args)
        return o, new, m, state.astype(jnp.bfloat16).astype(jnp.float32)

    return _kernels_with("_chunk_forward", rounded)


def product_solve():
    """Inside, (I + A)^-1 is (I - A)(I + A^2)(I + A^4) ... (I + A^32), the
    product of the whole chunk's powers: what `ops/kda._solve` is not."""
    def product(a, t_pos, i_pos, mm):
        x = -a
        t = jnp.where(t_pos == i_pos, 1.0, 0.0) + x
        n = 2
        while n < a.shape[-2]:
            x = mm(x, x)
            t = t + mm(t, x)
            n *= 2
        return t

    return _kernels_with("_solve", product)


def bounded_plan():
    """Inside, every call takes the bounded plan whatever its `g_min`: the
    control on `fast_decay`, whose g breaks that plan's contract."""
    return _kernels_with("plan_is_bounded", lambda g_min: True)


def unpacked_solve():
    """Inside, a grid step's rows go through `ops/kda._solve` one C x C
    product a row, as before PR 40, and not in lane-packed pairs: the same
    arithmetic, so o and the gradients have to EQUAL the kernels' own."""
    return _kernels_with("_solve_rows", lambda a, t_pos, i_pos, mm: K._solve(
        a, t_pos, i_pos, lambda x, y: mm(x, y, 1, 0, exact=True)))


def with_grads(fn, w):
    """-> a jitted (args) -> (o, the five gradients of sum(o * w))."""
    grads = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
                     argnums=(0, 1, 2, 3, 4))
    return jax.jit(lambda *a: (fn(*a),) + grads(*a))


def errors(got, want):
    def rel(a, b):
        a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    return {n: rel(a, b) for n, a, b in zip(NAMES, got, want)}


_WANT = {}


def _want(args, w, key):
    """The recurrence's o and gradients for these inputs, kept on the HOST
    under `key` (None: not kept): both plans are held to the same, and
    8,192 tokens one after another take the chip over a minute."""
    if key in _WANT:
        return _WANT[key]
    want = with_grads(lambda *a: K.kda_recurrence(*a)[0], w)(
        *(x.astype(jnp.float32) for x in args))
    if key is not None:
        _WANT[key] = want = jax.device_get(want)
    return want


def compare(args, w, control="bf16_state", key=None, **how):
    """The kernels (`how`: `kda`'s `use_pallas` / `interpret`) against the
    recurrence -> {"kernel": errors, `control`: errors}."""
    kernel = lambda *a: K.kda(*a, **how)  # noqa: E731
    want = _want(args, w, key)
    out = {"kernel": errors(with_grads(kernel, w)(*args), want)}
    with globals()[control]():
        out[control] = errors(with_grads(kernel, w)(*args), want)
    return out


def timed(fn, *args, n=10):
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / n * 1e3


# a kernel by the number of its outputs
KERNEL_OF_OUTPUTS = {2: "fwd_kernel_ms", 3: "states_ms", 5: "bwd_ms"}


def kernel_of(event_name):
    """A device event's name is its HLO text, `%name = (bf16[..]{..},
    f32[..]{..}) custom-call(...` -> which of `ops/kda.py`'s kernels it is,
    or None."""
    m = re.match(r"%[\w.\-]+ = \((.*?)\) custom-call\(", event_name)
    if not m or "tpu_custom_call" not in event_name:
        return None
    return KERNEL_OF_OUTPUTS.get(len(re.findall(r"\w+\[", m[1])))


def device_events(fn, *args, n=5):
    """`fn` (compiled already) traced over `n` calls -> [(name, ns)], every
    event of the device's "XLA Ops" lines; [] where there is no trace."""
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(
            d, "plugins", "profile", "*", "*.xplane.pb"))
        planes = ProfileData.from_file(paths[0]).planes if paths else []
        return [(e.name, e.duration_ns)
                for p in planes if p.name.startswith("/device:TPU:")
                for ln in p.lines if ln.name == "XLA Ops" for e in ln.events]


def kernel_ms(fn, *args, n=5, kernel_of=kernel_of):
    """`fn` (compiled already) traced over `n` calls -> the milliseconds
    of one event of each kernel (`kernel_of(event name)`: this tool's, or
    `tools/ssd_chip_check.py`'s) on the device's clock; {} where the trace
    holds no such event."""
    took = {}
    for name, ns in device_events(fn, *args, n=n):
        took.setdefault(kernel_of(name), []).append(ns * 1e-6)
    return {kernel: statistics.mean(ms) for kernel, ms in took.items()
            if kernel}


def check(shape, g_min, seed, kinds, only=None):
    """One shape under one plan -> the output's dict for it. `only`: the
    inputs to run, each as the whole run of this seed makes it, and no
    times."""
    if shape[2] > 2048 and "long_memory" in kinds:
        kinds = {**kinds, "long_memory": (LONG_MEMORY_BOUND_8K,
                                          kinds["long_memory"][1])}
    out = {"shape": list(shape), "g_min": g_min}
    how = dict(use_pallas=True, g_min=g_min)
    for i, (kind, (_, control)) in enumerate(kinds.items()):
        if only and kind not in only:
            continue
        args, w = inputs(kind, jax.random.fold_in(
            jax.random.PRNGKey(seed), i), *shape)
        out[kind] = compare(args, w, control, (tuple(shape), kind, seed),
                            **how)
        # what there is so far, should the call be cut
        print(json.dumps({"shape": out["shape"], "g_min": g_min,
                          kind: out[kind]}), flush=True)
    if only:
        kinds = {k: v for k, v in kinds.items() if k in only}
    else:
        out.update(times(shape, g_min, seed))
    out["ok"] = all(v <= bound for kind, (bound, _) in kinds.items()
                    for v in out[kind]["kernel"].values())
    # a NaN fails its bound too
    out["control_fails"] = all(
        any(not v <= kinds[kind][0] for v in out[kind][control].values())
        for kind, control in MUST_FAIL.items() if kind in kinds)
    return out


def times(shape, g_min, seed):
    """-> {"ms": the calls' and the kernels' times on `mixed`,
    "equals_unpacked_solve"} of one shape under one plan."""
    out = {}
    how = dict(use_pallas=True, g_min=g_min)
    args, w = inputs("mixed", jax.random.PRNGKey(seed), *shape)
    kernel = lambda *x: K.kda(*x, **how)  # noqa: E731
    grad = jax.grad(lambda *x: jnp.sum(kernel(*x).astype(jnp.float32) * w),
                    argnums=(0, 1, 2, 3, 4))
    out["ms"] = {
        "fwd": timed(jax.jit(kernel), *args),
        "fwd_xla": timed(jax.jit(lambda *x: K._kda_chunked(
            *x, K.plan_is_bounded(g_min))[0]), *args),
        "fwd_and_bwd": timed(jax.jit(grad), *args)}
    both = jax.jit(lambda *x: (kernel(*x), grad(*x)))
    got = jax.block_until_ready(both(*args))
    out["ms"].update(kernel_ms(both, *args))
    with unpacked_solve():
        out["equals_unpacked_solve"] = all(
            bool(jnp.all(a == b)) for a, b in zip(
                jax.tree.leaves(got), jax.tree.leaves(both(*args))))
    return out


CELL_CONFIG = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "configs", "solar-open2-250b-train-1chip.json")
CELL_CONTROLS = ("bf16_state", "bounded_plan")


def cell_check(seed):
    """-> {"path": {who: `path_errors` + `correct`}, "loss": {who: the
    loss's relative error}} for who in the program and the two controls."""
    from functools import partial

    from benchmarks import reference_solar2, train_kda_cell
    from benchmarks.train_cell import LOSS_TOLERANCE
    from ray_tpu.models import solar_open2

    with open(CELL_CONFIG) as f:
        config = json.load(f)
    with open(os.path.join(os.path.dirname(os.path.dirname(CELL_CONFIG)),
                           "traffic", "pretrain-8k-b1.json")) as f:
        traffic = json.load(f)
    program = config["program"]
    fields = {k: config[v] for k, v in program["fields_from"].items()}
    fields.update(program["fields"])
    cfg = {"model": fields, "model_module": program["module"],
           "config_class": program["config_class"],
           "reference_module": "benchmarks." + config["reference"],
           "trainer": {**config["trainer"], **traffic}, "seed": seed}
    whos = (None,) + CELL_CONTROLS
    entered = lambda who: globals()[who]() if who \
        else contextlib.nullcontext()  # noqa: E731
    out = {"seed": seed, "path": {}, "loss": {"tolerance": LOSS_TOLERANCE}}
    for who in whos:
        t = time.perf_counter()
        with entered(who):
            errors = train_kda_cell.path_errors(cfg)
        out["path"][who or "program"] = dict(
            errors, correct=train_kda_cell.within_limits(errors),
            seconds=time.perf_counter() - t)
        print(json.dumps({who or "program": out["path"][who or "program"]}),
              flush=True)
    # `train_cell`'s comparison: the first row of the timed batch
    model = solar_open2.SolarOpen2Config(**fields)
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    params = jax.jit(partial(solar_open2.init, model))(key)
    t = cfg["trainer"]
    toks = jax.random.randint(
        jax.random.fold_in(key, 1),
        (t["batches_in_cycle"], t["per_chip_batch"], t["seq"] + 1), 0,
        model.vocab_size)[0]
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    t0 = time.perf_counter()
    want = reference_solar2.loss(params, batch["inputs"], batch["targets"],
                                 fields)
    out["loss"].update(reference=want,
                       reference_seconds=time.perf_counter() - t0)
    for who in whos:
        with entered(who):
            got = float(jax.jit(partial(solar_open2.loss_fn, config=model))(
                params, batch))
        out["loss"][who or "program"] = {
            "loss": got, "rel_err": abs(got - want) / abs(want)}
    print(json.dumps({"loss": out["loss"]}), flush=True)
    out["ok"] = out["path"]["program"]["correct"] and not any(
        out["path"][who]["correct"] for who in CELL_CONTROLS)
    return out


SCOPE_PARTS = ("kda.prep", "kda.conv", "kda.gates", "kda.scan")


def _phase(scope):
    """An op's `op_name` -> forward, the forward's rerun under remat, or
    backward."""
    if "transpose(" not in scope:
        return "forward"
    return "rerun" if "rematted_computation" in scope else "backward"


def _fused_scopes(text):
    """A compiled step's text -> {fused computation: the KDA scopes of the
    instructions in its body}: a fusion's own metadata keeps one name."""
    inside, current = {}, None
    for line in text.splitlines():
        m = re.match(r"^%?(\S*fused\S*) \(", line)
        if m:
            current = inside.setdefault(m[1], set())
        elif line.startswith("}"):
            current = None
        elif current is not None:
            current.update(re.findall(r"kda\.\w+", "".join(
                re.findall(r'op_name="([^"]*)"', line))))
    return inside


def scope_split(cell, seed, steps=4, watchdog=900):
    """The cell's own train step (`benchmarks/train_cell.py`'s: the
    `program` group at the published widths, the traffic's B x S, AdamW,
    `train.make_train_step`), `steps` steps traced after two warm ones, under
    a watchdog that ends a hung process with its stack printed, and the
    device's time split by the `jax.named_scope` each op's metadata keeps in
    the COMPILED text (an event is named `%<instruction> = ...`): ms a step
    of `kda.conv` (the q, k, v side), `kda.gates`, `kda.scan` (the kernels)
    and, where a program has them, the `kda.prep` calls, each forward, rerun
    under remat and backward; a fusion that holds ops of several of these
    scopes is listed under all of them joined. -> the dict
    chiprun_out/kda_scope_split.<cell>.json keeps."""
    import faulthandler
    import importlib
    from functools import partial

    import optax

    from benchmarks import reduce_trace
    from ray_tpu import train
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import LogicalAxisRules
    from ray_tpu.train.step import _as_dict
    from tools import step_lowering_hash

    faulthandler.dump_traceback_later(watchdog, exit=True)
    (found,) = [c for c in step_lowering_hash.cells() if c[0]["name"] == cell]
    workload, config = found
    with open(os.path.join(step_lowering_hash.ROOT, "benchmarks", "traffic",
                           workload["traffic"] + ".json")) as f:
        traffic = json.load(f)
    program = config["program"]
    module = importlib.import_module(program["module"])
    fields = {k: config[v] for k, v in program["fields_from"].items()}
    fields.update(program["fields"])
    model = getattr(module, program["config_class"])(**fields)
    mesh = build_mesh(MeshConfig(**config["mesh"]), devices=jax.devices()[:1])
    rules = LogicalAxisRules()
    t = config["trainer"]
    opt = optax.adamw(t["learning_rate"], weight_decay=t["weight_decay"])
    key = jax.random.fold_in(jax.random.PRNGKey(seed % 2**31), seed // 2**31)
    state, shardings = train.init_train_state(
        partial(module.init, model), opt, module.param_logical_axes(model),
        mesh, key, rules)
    bs = train.batch_sharding(mesh, rules)
    step = train.make_train_step(
        partial(module.loss_fn, config=model, mesh=mesh, rules=rules), opt,
        shardings, batch_sharding={"inputs": bs, "targets": bs})
    toks = jax.random.randint(
        jax.random.fold_in(key, 1),
        (traffic["per_chip_batch"], traffic["seq"] + 1), 0, model.vocab_size)
    batch = jax.device_put({"inputs": toks[:, :-1], "targets": toks[:, 1:]},
                           bs)
    compiled = step.lower(state, batch).compile()
    state = _as_dict(state)
    text = compiled.as_text()
    print("compiled", flush=True)
    fused = _fused_scopes(text)
    scope_of = {}
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%([\w.\-]+) = ", line)
        if not m or " parameter(" in line:
            continue
        name = "".join(re.findall(r'op_name="([^"]*)"', line)[:1])
        parts = set(re.findall(r"kda\.\w+", name))
        called = re.search(r"calls=%?([\w.\-]+)", line)
        if called and called[1] in fused:
            parts |= fused[called[1]]
        scope_of[m[1]] = ("+".join(sorted(parts & set(SCOPE_PARTS))),
                          _phase(name), name)
    losses = []
    for i in range(2 + steps):
        if i == 2:
            where = tempfile.mkdtemp()
            jax.profiler.start_trace(where)
        start = time.perf_counter()
        state, m = compiled(state, batch)
        losses.append(float(m["loss"]))
        print(f"step {i} loss {losses[-1]:.4f} "
              f"{1e3 * (time.perf_counter() - start):.1f} ms", flush=True)
    jax.profiler.stop_trace()
    faulthandler.cancel_dump_traceback_later()
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(where, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    events = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
              for p in ProfileData.from_file(path).planes
              if p.name.startswith("/device:TPU:")
              for ln in p.lines if ln.name == "XLA Ops" for e in ln.events]
    events, selfs = reduce_trace.self_times(events)
    split, by_op, busy = {}, {}, 0.0
    for (_, _, name), took in zip(events, selfs):
        ms = 1e3 * took / steps
        busy += ms
        m = re.match(r"%([\w.\-]+) = ", name)
        part, phase, scope = scope_of.get(m[1] if m else "", ("", "", ""))
        op = by_op.setdefault(m[1] if m else name[:40], {
            "ms": 0.0, "op": reduce_trace.short_op(name), "part": part,
            "phase": phase, "scope": scope[-120:]})
        op["ms"] += ms
        if part:
            at = split.setdefault(part, {})
            at[phase] = at.get(phase, 0.0) + ms
    for part in split.values():
        part["all"] = sum(part.values())
    top = sorted(by_op.values(), key=lambda o: -o["ms"])
    return {"cell": cell, "seed": seed, "steps": steps, "losses": losses,
            "device": jax.devices()[0].device_kind,
            "busy_ms_a_step": busy, "ms_a_step": split,
            "top_in_scopes": [o for o in top if o["part"]][:40],
            "top_ops": top[:40], "all_ops": top}


def prep_check(seed, calls=5, tile=None):
    """`ops/kda_prep.py`'s two calls alone at both cells' shapes against
    `mixers.kda_operands`, the `jnp` lines, on the chip -> {shape: q, k, v
    against the lines' (the share of bf16 values that are equal, the largest
    difference in ulps of bf16), dx and d taps against the lines' own vjp in
    float32 (relative, Frobenius; `lines_*`: what the lines in bf16 read
    against the same), and by the DEVICE'S clock, ms a call: the forward and
    forward + backward programs of each, the calls' own events apart, with
    the bytes a call has to move (a read and a write a tensor forward; x,
    the cotangent and dx backward) and their share of the HBM's peak}."""
    from benchmarks import peaks
    from ray_tpu.models import mixers
    from ray_tpu.ops import kda_prep

    peak = peaks.PEAKS[jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    dtype = jnp.bfloat16
    if tile:   # for tuning: the module's own is what a model runs
        kda_prep.TOKEN_TILE = tile
    out = {"tile": kda_prep.TOKEN_TILE}
    for name, (b, h, s, d) in SHAPES.items():
        ks = jax.random.split(jax.random.PRNGKey(seed), 9)
        xs = [jax.random.normal(ks[i], (b, s, h, d)).astype(dtype)
              for i in range(3)]
        taps = [(0.5 * jax.random.normal(ks[3 + i], (4, h, d))).astype(dtype)
                for i in range(3)]
        cots = tuple(jax.random.normal(ks[6 + i], (b, h, s, d)).astype(dtype)
                     for i in range(3))
        assert kda_prep.fused(xs[0].shape, taps[0])
        # the calls read a projection's rows [B, S, H D]
        rows = lambda x: x.reshape(x.shape[:2] + (-1,))  # noqa: E731
        prep = lambda xs, taps, dt: kda_prep.prep(  # noqa: E731
            [rows(x) for x in xs], taps, dt)

        def both(fn, dt):
            def run(xs, taps, cots):
                o, vjp = jax.vjp(lambda xs, taps: fn(xs, taps, dt), xs, taps)
                return o, vjp(cots)
            return jax.jit(run)

        fwd = {"calls": jax.jit(lambda xs, taps: prep(xs, taps, dtype)),
               "lines": jax.jit(lambda xs, taps: mixers.kda_operands(
                   xs, taps, dtype))}
        bwd = {"calls": both(prep, dtype),
               "lines": both(mixers.kda_operands, dtype)}
        got, (got_dx, got_dtaps) = bwd["calls"](xs, taps, cots)
        want, (lines_dx, lines_dtaps) = bwd["lines"](xs, taps, cots)
        f32 = lambda tree: jax.tree.map(  # noqa: E731
            lambda a: a.astype(jnp.float32), tree)
        _, (want_dx, want_dtaps) = both(mixers.kda_operands, jnp.float32)(
            *f32((xs, taps, cots)))
        rel = lambda a, b: float(  # noqa: E731
            jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32))
            / jnp.linalg.norm(b.astype(jnp.float32)))
        bits = lambda a: jax.lax.bitcast_convert_type(  # noqa: E731
            a, jnp.int16).astype(jnp.int32)
        at = out[name] = {"shape": [b, h, s, d], "values": {}, "grads": {}}
        for n, a, w in zip("qkv", got, want):
            at["values"][n] = {
                "equal_share": float(jnp.mean(a == w)),
                "max_ulps": int(jnp.max(jnp.abs(bits(a) - bits(w)))),
                "rel": rel(a, w)}
        for n, a, w, ln in zip(("dx_q", "dx_k", "dx_v"), got_dx, want_dx,
                               lines_dx):
            at["grads"][n] = {"rel": rel(a, w), "lines_rel": rel(ln, w)}
        for n, a, w, ln in zip(("dtaps_q", "dtaps_k", "dtaps_v"), got_dtaps,
                               want_dtaps, lines_dtaps):
            at["grads"][n] = {"rel": rel(a, w), "lines_rel": rel(ln, w)}
        tensor = b * s * h * d * 2
        at["bytes"] = {"fwd": 6 * tensor, "fwd_and_bwd": 15 * tensor}
        at["ms"] = {}
        def timed_on_device(kind, fns, args):
            """ms a call of each program of `fns`, and of the calls' own
            events (named by their scope) with their share of the peak."""
            for who, fn in fns.items():
                jax.block_until_ready(fn(*args))
                events = device_events(fn, *args, n=calls)
                at["ms"][f"{kind}_{who}"] = \
                    sum(ns for _, ns in events) * 1e-6 / calls
                own = sum(ns for e, ns in events
                          if "kda.prep" in e.split(" = ")[0]) * 1e-6 / calls
                if who == "calls" and own:
                    at["ms"][f"{kind}_calls_own"] = own
                    at[f"{kind}_hbm_share"] = \
                        at["bytes"][kind] / (own * 1e-3) / peak

        timed_on_device("fwd", fwd, (xs, taps))
        timed_on_device("fwd_and_bwd", bwd, (xs, taps, cots))
        # the decay gate under the cell's form: Ling's bounded sigmoid,
        # Solar's softplus
        bound = {"ling": K.G_MIN_BOUNDED, "solar": None}[name]
        a = (2.0 * jax.random.normal(ks[0], (b, s, h, d))).astype(dtype)
        bias = -jax.random.uniform(ks[1], (h, d), minval=1.0, maxval=5.0)
        a_log = jnp.log(jax.random.uniform(ks[2], (h,), minval=1.0,
                                           maxval=16.0))
        w = jax.random.normal(ks[3], (b, h, s, d))

        def gate_both(fn):
            def run(a, bias, a_log, w):
                g, vjp = jax.vjp(lambda *x: fn(*x, bound), a, bias, a_log)
                return g, vjp(w)
            return jax.jit(run)

        gates = {"calls": gate_both(lambda a, *x: kda_prep.gate(rows(a), *x)),
                 "lines": gate_both(mixers.kda_decay)}
        got_g, got_grads = gates["calls"](a, bias, a_log, w)
        want_g, _ = gates["lines"](a, bias, a_log, w)
        _, want_grads = gates["lines"](a.astype(jnp.float32), bias, a_log, w)
        at["gate"] = {"g": rel(got_g, want_g), **{
            n: rel(x, y) for n, x, y in zip(("da", "d_dt_bias", "d_a_log"),
                                            got_grads, want_grads)}}
        at["bytes"]["gate_fwd_and_bwd"] = 7 * tensor   # a 1, g 2; dg 2, a, da
        timed_on_device("gate_fwd_and_bwd", gates, (a, bias, a_log, w))
        print(json.dumps({name: at}), flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, nargs="+", default=[39])
    ap.add_argument("--kinds", nargs="+", choices=list(ANY_KINDS),
                    help="these inputs only")
    ap.add_argument("--cell", action="store_true", help="what the Solar "
                    "cell's `correct` compares, program and controls, a "
                    "seed at a time, and nothing else")
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--times-only", action="store_true", help="the `ms` of "
                    "each shape and plan and nothing compared (exit 0): a "
                    "minute, for tuning a kernel")
    ap.add_argument("--scopes", metavar="CELL", nargs="+", help="and nothing "
                    "else: each cell's own train step traced for four "
                    "steps under a watchdog, the device's ms a step split "
                    "by the scopes kda.conv / kda.gates / kda.scan / "
                    "kda.prep, forward, rerun and backward")
    ap.add_argument("--tile", type=int, help="with --prep: the calls at "
                    "this token tile, not the module's (for tuning)")
    ap.add_argument("--prep", action="store_true", help="and nothing else: "
                    "`ops/kda_prep.py`'s calls alone at both cells' shapes "
                    "against the `jnp` lines, values, gradients and the "
                    "device's ms a call")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print(f"kda_chip_check: backend {jax.default_backend()!r}, not a TPU: "
              "run it through the chip tool", file=sys.stderr)
        return 3
    os.makedirs("chiprun_out", exist_ok=True)
    if a.prep:
        out = prep_check(a.seed[0], tile=a.tile)
        name = f"kda_prep_check.{a.tile}.json" if a.tile \
            else "kda_prep_check.json"
        with open("chiprun_out/" + name, "w") as f:
            json.dump(out, f, indent=1)
        shapes = [at for at in out.values() if isinstance(at, dict)]
        # a call's dx is rounded once: no worse than the lines' own
        return 0 if all(
            v["max_ulps"] <= 1 for at in shapes
            for v in at["values"].values()) and all(
            g["rel"] <= max(2e-3, g["lines_rel"]) for at in shapes
            for g in at["grads"].values()) and all(
            at["gate"]["g"] <= 1e-5 and at["gate"]["da"] <= 2e-3
            for at in shapes) else 1
    if a.scopes:
        for cell in a.scopes:
            out = scope_split(cell, a.seed[0])
            # every op, for a second reading without a second run
            with open(f"chiprun_out/kda_scope_split.{cell}.ops.json",
                      "w") as f:
                json.dump(out.pop("all_ops"), f)
            with open(f"chiprun_out/kda_scope_split.{cell}.json", "w") as f:
                json.dump(out, f, indent=1)
            print(json.dumps({k: out[k] for k in (
                "cell", "losses", "busy_ms_a_step", "ms_a_step")}))
        return 0
    if a.cell:
        cells = [cell_check(seed) for seed in a.seed]
        with open("chiprun_out/kda_cell_check.json", "w") as f:
            json.dump(cells, f, indent=1)
        return 0 if all(c["ok"] for c in cells) else 1
    (seed,) = a.seed
    kinds = lambda all_: {} if a.times_only else all_  # noqa: E731
    out = {"device": jax.devices()[0].device_kind, "seed": seed,
           "bound": BOUND, "aligned_bound": ALIGNED_BOUND}
    checks = []
    for name in a.shapes:
        at = out if name == "ling" else out.setdefault(name, {})
        at.update(check(SHAPES[name], K.G_MIN_BOUNDED, seed, kinds(KINDS),
                        a.kinds))
        at["any_decay"] = check(SHAPES[name], None, seed, kinds(ANY_KINDS),
                                a.kinds)
        checks += [at, at["any_decay"]]
    with open("chiprun_out/kda_chip_check.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if all(c["ok"] and c["control_fails"]
                    and c.get("equals_unpacked_solve", True)
                    for c in checks) else 1


if __name__ == "__main__":
    sys.exit(main())
