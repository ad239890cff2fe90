#!/usr/bin/env python3
"""python3 tools/ssd_chip_check.py [--seed n] [--batch b --seq s --heads h
--head-dim p --groups g --state n --chunk q]: `ops/ssd.py`'s three Pallas
kernels alone ON THE CHIP, at the Nemotron cell's shape unless told (x `[2,
2048, 128, 64]`, state 128, 8 groups, chunk 128; the Granite cell's is
`--batch 1 --seq 32768 --heads 64 --groups 1 --chunk 256`, ONE group of
4,096 lanes that the kernels walk in four head blocks): any other backend exits 3 before
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
event has; beside them `bound_ms`, the least the chip could take for the
forward and for the backward pass at this shape and chunk (the chunked
form's matmuls over the peak, or its operands' bytes once over the HBM's
bandwidth: `benchmarks/opcount_nemotron3.py`'s count). Past S 4,096 the
`long_memory` recurrence is evaluated on the host's CPU (`main` on why;
`recurrence_here_against_cpu` is what the TPU's own evaluation of the
definition is off by). Writes
chiprun_out/ssd_chip_check.json (`--out` names another file there).
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
           dtype=jnp.bfloat16, chunk=S.CHUNK):
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
        late = (jnp.arange(s) >= chunk)[None, :, None]
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


def compare(args, w, reference_on_cpu=False, **how):
    """The kernels (`how`: `ssd_scan`'s `use_pallas` / `interpret` /
    `chunk`) against the recurrence -> {"kernel": errors, "bf16_state":
    errors}. `reference_on_cpu`: the recurrence runs on the host's CPU
    backend (`main` on why a long sequence needs that)."""
    kernel = lambda *a: S.ssd_scan(*a, **how)[0]  # noqa: E731
    f32_args = [v.astype(jnp.float32) for v in args]
    reference = with_grads(lambda *a: S.ssd_recurrence(*a)[0], w)
    if reference_on_cpu:
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            reference = with_grads(lambda *a: S.ssd_recurrence(*a)[0],
                                   jax.device_put(w, cpu))
            want = reference(*jax.device_put(f32_args, cpu))
        want = jax.device_put(want, jax.devices()[0])
    else:
        want = reference(*f32_args)
    out = {"kernel": errors(with_grads(kernel, w)(*args), want)}
    with bf16_state():
        out["bf16_state"] = errors(with_grads(kernel, w)(*args), want)
    if reference_on_cpu:
        # the same recurrence where the kernels run, forward: what the
        # backend's own exp() costs the definition over this many tokens
        here = jax.jit(lambda *a: S.ssd_recurrence(*a)[0])(*f32_args)
        out["recurrence_here_against_cpu"] = errors((here,), want[:1])
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


def bound_ms(b, s, h, p, g, n, chunk):
    """-> {"fwd": ms, "bwd": ms}: the larger of the chunked form's matmuls
    over the v5e's peak and the operands' bytes over its bandwidth."""
    from benchmarks import opcount_nemotron3 as counts
    from benchmarks import peaks

    peak = peaks.peaks("TPU v5 lite")
    ops = b * g * -(-s // chunk) * counts.ssd_chunk_ops(h // g, p, n, chunk)
    out = {}
    for name, fn, times in (("fwd", counts.ssd_fwd, 1),
                            ("bwd", counts.ssd_bwd, 2)):
        out[name] = 1e3 * counts.bound_seconds(
            times * ops, fn(b, h, s, p, g, n)[1], peak)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=43)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=128)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--state", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=S.CHUNK)
    ap.add_argument("--out", default="ssd_chip_check.json")
    a = ap.parse_args()
    shape = dict(b=a.batch, s=a.seq, h=a.heads, p=a.head_dim, g=a.groups,
                 n=a.state, chunk=a.chunk)
    if jax.default_backend() != "tpu":
        print(f"ssd_chip_check: backend {jax.default_backend()!r}, not a TPU: "
              "run it through the chip tool", file=sys.stderr)
        return 3
    out = {"device": jax.devices()[0].device_kind, "seed": a.seed,
           "bound": BOUND, "chunk": a.chunk, "groups": a.groups,
           "head_blocks": S._head_blocks(a.heads // a.groups, a.head_dim)}
    for i, kind in enumerate(KINDS):
        args, w = inputs(kind, jax.random.fold_in(
            jax.random.PRNGKey(a.seed), i), **shape)
        # `long_memory` multiplies a state by exp(a_t) of nearly the same
        # tiny a_t at every token: an error of the backend's exp() a few
        # float32 ulps large adds up over the tokens in ONE direction. At
        # S 32,768 the recurrence ON THE TPU is 2.1% from the kernels at
        # a chunk of 128 and of 256 alike (my chip run, PR 55), 4.7e-3 at S
        # 2,048; the kernels take exp() of a chunk's running sum, a few
        # hundred times a sequence, so past 4,096 tokens the definition is
        # evaluated on the host's CPU, minutes of it
        out[kind] = compare(
            args, w, use_pallas=True, chunk=a.chunk,
            reference_on_cpu=kind == "long_memory" and a.seq > 4096)
    args, w = inputs("mixed", jax.random.PRNGKey(a.seed), **shape)
    out["shape"] = list(args[0].shape)
    kernel = lambda *v: S.ssd_scan(  # noqa: E731
        *v, use_pallas=True, chunk=a.chunk)[0]
    grad = jax.grad(lambda *v: jnp.sum(kernel(*v).astype(jnp.float32) * w),
                    argnums=(0, 1, 2, 3, 4))
    out["ms"] = {
        "fwd": timed(jax.jit(kernel), *args),
        "fwd_and_bwd": timed(jax.jit(grad), *args)}
    if a.batch * a.seq * a.heads <= 2 * 2048 * 128:
        # the `jnp` form holds a [chunk, chunk] decay a head and chunk in
        # float32: 2 GiB at the Granite cell's shape, not timed there
        out["ms"]["fwd_xla"] = timed(jax.jit(
            lambda *v: S._ssd_chunked(*v, chunk=a.chunk)[0]), *args)
    both = jax.jit(lambda *v: (kernel(*v), grad(*v)))
    jax.block_until_ready(both(*args))
    out["ms"].update(kernel_ms(both, *args, kernel_of=kernel_of))
    out["bound_ms"] = bound_ms(**shape)
    out["ok"] = all(v <= BOUND for kind in KINDS
                    for v in out[kind]["kernel"].values())
    # a NaN fails its bound too
    out["control_fails"] = any(
        not v <= BOUND for v in out["long_memory"]["bf16_state"].values())
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", os.path.basename(a.out)), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if out["ok"] and out["control_fails"] else 1


if __name__ == "__main__":
    sys.exit(main())
