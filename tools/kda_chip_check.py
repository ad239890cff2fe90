#!/usr/bin/env python3
"""python3 tools/kda_chip_check.py [--seed n]: `ops/kda.py`'s three Pallas
kernels alone at the Ling cell's shape, `[4, 32, 2048, 128]`, ON THE CHIP:
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

THE BOUND, 7e-3 a tensor (PERF.md section 6, PR 39, has the readings). The
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
    if kind == "aligned_keys":
        k = l2(jax.random.normal(ks[6], (b, h, 1, d))
               + 0.4 * jax.random.normal(ks[1], (b, h, s, d)))
        g = -5e-2 * jax.random.uniform(ks[3], g.shape, minval=0.01)
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, s)) + 4.0)
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
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    return {n: rel(a, b) for n, a, b in zip(NAMES, got, want)}


def compare(args, w, control="bf16_state", **how):
    """The kernels (`how`: `kda`'s `use_pallas` / `interpret`) against the
    recurrence -> {"kernel": errors, `control`: errors}."""
    kernel = lambda *a: K.kda(*a, **how)  # noqa: E731
    want = with_grads(lambda *a: K.kda_recurrence(*a)[0], w)(
        *(x.astype(jnp.float32) for x in args))
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=39)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print(f"kda_chip_check: backend {jax.default_backend()!r}, not a TPU: "
              "run it through the chip tool", file=sys.stderr)
        return 3
    out = {"device": jax.devices()[0].device_kind, "seed": a.seed,
           "bound": BOUND, "aligned_bound": ALIGNED_BOUND}
    for i, (kind, (_, control)) in enumerate(KINDS.items()):
        args, w = inputs(kind, jax.random.fold_in(
            jax.random.PRNGKey(a.seed), i))
        out[kind] = compare(args, w, control, use_pallas=True)
    args, w = inputs("mixed", jax.random.PRNGKey(a.seed))
    out["shape"] = list(args[0].shape)
    kernel = lambda *x: K.kda(*x, use_pallas=True)  # noqa: E731
    grad = jax.grad(lambda *x: jnp.sum(kernel(*x).astype(jnp.float32) * w),
                    argnums=(0, 1, 2, 3, 4))
    out["ms"] = {
        "fwd": timed(jax.jit(kernel), *args),
        "fwd_xla": timed(jax.jit(lambda *x: K._kda_chunked(*x)[0]), *args),
        "fwd_and_bwd": timed(jax.jit(grad), *args)}
    both = jax.jit(lambda *x: (kernel(*x), grad(*x)))
    got = jax.block_until_ready(both(*args))
    out["ms"].update(kernel_ms(both, *args))
    with unpacked_solve():
        out["equals_unpacked_solve"] = all(
            bool(jnp.all(a == b)) for a, b in zip(
                jax.tree.leaves(got), jax.tree.leaves(both(*args))))
    out["ok"] = all(v <= bound for kind, (bound, _) in KINDS.items()
                    for v in out[kind]["kernel"].values())
    # a NaN fails its bound too
    out["control_fails"] = all(
        any(not v <= KINDS[kind][0] for v in out[kind][control].values())
        for kind, control in (("long_memory", "bf16_state"),
                              ("aligned_keys", "product_solve")))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_chip_check.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if out["ok"] and out["control_fails"] \
        and out["equals_unpacked_solve"] else 1


if __name__ == "__main__":
    sys.exit(main())
