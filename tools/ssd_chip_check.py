#!/usr/bin/env python3
"""python3 tools/ssd_chip_check.py [--seed n]: `ops/ssd.py`'s
three Pallas kernels alone at the Nemotron cell's shape, x `[2, 2048, 128,
64]`, state 128, 8 groups, ON THE CHIP: any other backend exits 3 before
anything is computed, and every call here passes `use_pallas=True`, so no
number of this tool ever comes from the `jnp` form. y and the five
gradients against the token-by-token float32 recurrence (relative error in
the Frobenius norm, a tensor at a time) on two kinds of input, the same with
a BF16 STATE (the kernels themselves, the state a chunk hands the next
rounded to bf16: the control, which has to FAIL the bound), and the calls'
times on the host's clock and the device's.

THE INPUTS. `mixed`: what a layer sees at initialisation: Delta =
softplus(dt + dt_bias) around a step drawn log-uniform in [0.001, 0.1], A in
-[1, 16] a head, so a head's decay a token runs from exp(-0.001) to
exp(-1.6): from "keeps for thousands of tokens" to "forgets within a
chunk". `long_memory`: the first chunk writes the state as `mixed` does and
every later token nearly keeps it (a -1.2e-5 to -1.5e-5 a token, Delta
1e-6): a chunk changes the state by at most 1.92e-3 of itself, under half a
bf16 ulp (2^-9 to 2^-8 of the value), so a state rounded between chunks
STANDS STILL where the float32 one has decayed by ~2.7% at the sequence's
end.

THE BOUND, `BOUND` a tensor (PERF.md section 6, PR 43, has the readings).
The chunked form rounds each matmul's operands to bf16 (2^-9 a value) with
float32 accumulation, through a chain of two matmuls a chunk (C B^T, then
the masked scores against Delta x; or the state's read), and the backward
kernels round the cotangents the same way. Exit 1 if a tensor of the
float32 state misses the bound on any input or the bf16 state passes
everywhere on `long_memory`.

`ms`: `fwd` the forward kernel, `fwd_xla` the chunked form in XLA,
`fwd_and_bwd` the gradient of a LINEAR function of y: the forward is dead
code there, so it is the two backward kernels, on the host's clock; and the
three kernels APART on the device's, from a short trace of value and
gradient: `fwd_kernel_ms`, `states_ms` (the backward pass's walk forwards)
and `bwd_ms` (its walk backwards), told apart by how many outputs a Pallas
event has. Writes chiprun_out/ssd_chip_check.json.
"""
import argparse
import contextlib
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import ssd as S  # noqa: E402
from tools.kda_chip_check import kernel_ms, timed, with_grads  # noqa: E402

BOUND = 8e-3
NAMES = ("y", "dx", "ddelta", "da", "db", "dc")
KINDS = ("mixed", "long_memory")


def inputs(kind, key, b=2, s=2048, h=128, p=64, g=8, n=128,
           dtype=jnp.bfloat16):
    """-> ((x, b, c in `dtype`, delta, a float32, in `ssd_scan`'s order),
    the cotangent of y)."""
    ks = jax.random.split(key, 8)
    x = jax.random.normal(ks[0], (b, s, h, p))
    step = jnp.exp(jax.random.uniform(ks[1], (h,), minval=jnp.log(1e-3),
                                      maxval=jnp.log(0.1)))
    delta = jax.nn.softplus(jax.random.normal(ks[2], (b, s, h))
                            + step + jnp.log(-jnp.expm1(-step)))
    a = -jax.random.uniform(ks[3], (h,), minval=1.0, maxval=16.0) * delta
    bm = jax.nn.silu(jax.random.normal(ks[4], (b, s, g, n)))
    cm = jax.nn.silu(jax.random.normal(ks[5], (b, s, g, n)))
    if kind == "long_memory":
        late = (jnp.arange(s) >= S.CHUNK)[None, :, None]
        a = jnp.where(late, -1e-5 * jax.random.uniform(
            ks[7], a.shape, minval=1.2, maxval=1.5), a)
        delta = jnp.where(late, 1e-6, delta)
    w = jax.random.normal(ks[6], (b, s, h, p))
    return (x.astype(dtype), delta, a, bm.astype(dtype), cm.astype(dtype)), w


@contextlib.contextmanager
def bf16_state():
    """Inside, the kernels carry their state in bf16: `_chunk_forward` (a
    chunk's step in the forward kernel and in the backward pass's first
    walk) hands on a rounded state. The jit caches are dropped on the way
    in and out: the kernels' traces are cached by shape."""
    step = S._chunk_forward

    def rounded(*args, **kw):
        y, state = step(*args, **kw)
        return y, state.astype(jnp.bfloat16).astype(jnp.float32)

    S._chunk_forward = rounded
    jax.clear_caches()
    try:
        yield
    finally:
        S._chunk_forward = step
        jax.clear_caches()


def errors(got, want):
    def rel(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    return {n: rel(a, b) for n, a, b in zip(NAMES, got, want)}


def compare(args, w, **how):
    """The kernels (`how`: `ssd_scan`'s `use_pallas` / `interpret`) against
    the recurrence -> {"kernel": errors, "bf16_state": errors}."""
    kernel = lambda *a: S.ssd_scan(*a, **how)[0]  # noqa: E731
    want = with_grads(lambda *a: S.ssd_recurrence(*a)[0], w)(
        *(v.astype(jnp.float32) for v in args))
    out = {"kernel": errors(with_grads(kernel, w)(*args), want)}
    with bf16_state():
        out["bf16_state"] = errors(with_grads(kernel, w)(*args), want)
    return out


def kernel_of(event_name):
    """A device event's name is its HLO text -> which of `ops/ssd.py`'s
    kernels it is, by the number of its outputs, or None."""
    m = re.match(r"%[\w.\-]+ = (\(.*?\)|\w+\[[\d,]*\]\S*) custom-call\(",
                 event_name)
    if not m or "tpu_custom_call" not in event_name:
        return None
    return {1: "states_ms", 2: "fwd_kernel_ms", 6: "bwd_ms"}.get(
        len(re.findall(r"\w+\[", m[1])))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=43)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print(f"ssd_chip_check: backend {jax.default_backend()!r}, not a TPU: "
              "run it through the chip tool", file=sys.stderr)
        return 3
    out = {"device": jax.devices()[0].device_kind, "seed": a.seed,
           "bound": BOUND}
    for i, kind in enumerate(KINDS):
        args, w = inputs(kind, jax.random.fold_in(
            jax.random.PRNGKey(a.seed), i))
        out[kind] = compare(args, w, use_pallas=True)
    args, w = inputs("mixed", jax.random.PRNGKey(a.seed))
    out["shape"] = list(args[0].shape)
    kernel = lambda *v: S.ssd_scan(*v, use_pallas=True)[0]  # noqa: E731
    grad = jax.grad(lambda *v: jnp.sum(kernel(*v).astype(jnp.float32) * w),
                    argnums=(0, 1, 2, 3, 4))
    out["ms"] = {
        "fwd": timed(jax.jit(kernel), *args),
        "fwd_xla": timed(jax.jit(lambda *v: S._ssd_chunked(*v)[0]), *args),
        "fwd_and_bwd": timed(jax.jit(grad), *args)}
    both = jax.jit(lambda *v: (kernel(*v), grad(*v)))
    jax.block_until_ready(both(*args))
    out["ms"].update(kernel_ms(both, *args, kernel_of=kernel_of))
    out["ok"] = all(v <= BOUND for kind in KINDS
                    for v in out[kind]["kernel"].values())
    # a NaN fails its bound too
    out["control_fails"] = any(
        not v <= BOUND for v in out["long_memory"]["bf16_state"].values())
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ssd_chip_check.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if out["ok"] and out["control_fails"] else 1


if __name__ == "__main__":
    sys.exit(main())
