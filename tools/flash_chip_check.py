#!/usr/bin/env python3
"""python3 tools/flash_chip_check.py [--seq s --heads h --kv-heads g
--head-dim d --scale x --seed n]: ONE causal flash call of
`ops/flash_attention.py` alone ON THE CHIP (any other backend exits 3), at
the Granite cell's shape unless told: q `[1, 32768, 32, 64]`, k and v
`[1, 32768, 8, 64]`, scale 1 / 64, the first call with a 64-wide head as its
only part and the first past S 16,384.

Against a plain oracle that such a sequence still fits: the cotangent of o is
zero outside `BLOCKS` blocks of 256 query rows (the first, one in the middle,
the last), so o is compared on those rows, dq on those rows (it is zero
elsewhere, which is checked too), dk and dv whole: the oracle is float32
`jnp` softmax attention of each block against ALL keys under the block's
dense causal mask, differentiated by XLA. Relative error in the Frobenius
norm a tensor, bf16 operands on both sides; `BOUND` is what bf16 products
with float32 accumulation keep. The control that has to FAIL: the same
oracle at scale d ** -0.5. `ms`: the three kernels on the device's clock
from a short trace, beside `bound_ms` (`benchmarks/opcount_granite4.py`'s
kept scores over the peak, or the operands' bytes). Writes
chiprun_out/flash_chip_check.json.
"""
import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops.flash_attention import flash_attention  # noqa: E402
from tools.kda_chip_check import kernel_ms  # noqa: E402

BOUND = 2e-2
ROWS = 256
NAMES = ("o", "dq", "dk", "dv")


def oracle(q, k, v, blocks, scale):
    """-> o of the query rows of `blocks` [len(blocks) * ROWS, H, D],
    float32: softmax(scale q k^T + causal) v a block, a KV head at a time."""
    s, h = q.shape[1], q.shape[2]
    rep = h // k.shape[2]
    q, k, v = (x[0].astype(jnp.float32) for x in (q, k, v))
    out = []
    with jax.default_matmul_precision("highest"):
        for blk in blocks:
            rows = jnp.arange(blk * ROWS, (blk + 1) * ROWS)
            keep = rows[:, None] >= jnp.arange(s)[None]
            heads = []
            for g in range(k.shape[1]):
                scores = jnp.einsum(
                    "qrd,td->rqt", q[rows][:, g * rep:(g + 1) * rep],
                    k[:, g]) * scale
                probs = jax.nn.softmax(
                    jnp.where(keep[None], scores, -jnp.inf), -1)
                heads.append(jnp.einsum("rqt,td->qrd", probs, v[:, g]))
            out.append(jnp.concatenate(heads, 1))
    return jnp.concatenate(out)


def kernel_of(event_name):
    m = re.match(r"%[\w.\-]+ = (\(.*?\)|\w+\[[\d,]*\]\S*) custom-call\(",
                 event_name)
    if not m or "tpu_custom_call" not in event_name:
        return None
    outputs = re.findall(r"(\w+)\[", m[1])
    return {("bf16", "f32"): "fwd_ms", ("bf16",): "dq_ms",
            ("bf16", "bf16"): "dkv_ms"}.get(tuple(outputs))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=55)
    ap.add_argument("--seq", type=int, default=32768)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--scale", type=float, default=0.015625)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print(f"flash_chip_check: backend {jax.default_backend()!r}, not a "
              "TPU: run it through the chip tool", file=sys.stderr)
        return 3
    from benchmarks import opcount_granite4 as counts
    from benchmarks import peaks

    s, h, g, d = a.seq, a.heads, a.kv_heads, a.head_dim
    n = s // ROWS
    blocks = sorted({0, n // 2, n - 1})
    ks = jax.random.split(jax.random.PRNGKey(a.seed), 4)
    bf16 = jnp.bfloat16
    # keys of RMS 8: at scale 1 / 64 the scores have RMS ~1 over 64 channels
    q = jax.random.normal(ks[0], (1, s, h, d)).astype(bf16)
    k = (8.0 * jax.random.normal(ks[1], (1, s, g, d))).astype(bf16)
    v = jax.random.normal(ks[2], (1, s, g, d)).astype(bf16)
    picked = jnp.concatenate(
        [jnp.arange(b * ROWS, (b + 1) * ROWS) for b in blocks])
    w_rows = jax.random.normal(ks[3], (len(picked), h, d))
    w = jnp.zeros((1, s, h, d)).at[0, picked].set(w_rows)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=a.scale,
                               use_pallas=True)

    def run(fn, loss):
        grads = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
        return (jax.jit(fn)(q, k, v),) + grads

    got = run(lambda q, k, v: kernel(q, k, v)[0, picked],
              lambda q, k, v: jnp.sum(kernel(q, k, v).astype(jnp.float32) * w))

    def against(scale):
        want = run(lambda q, k, v: oracle(q, k, v, blocks, scale),
                   lambda q, k, v: jnp.sum(oracle(q, k, v, blocks, scale)
                                           * w_rows))
        rel = lambda x, y: float(  # noqa: E731
            jnp.linalg.norm(x.astype(jnp.float32) - y.astype(jnp.float32))
            / jnp.linalg.norm(y.astype(jnp.float32)))
        return {name: rel(x, y) for name, x, y in zip(NAMES, got, want)}

    out = {"device": jax.devices()[0].device_kind, "seed": a.seed,
           "shape": [1, s, h, d], "kv_heads": g, "scale": a.scale,
           "bound": BOUND, "rows_compared": len(picked),
           "kernel": against(a.scale),
           "scale_of_sqrt_d": against(d ** -0.5)}
    rest = jnp.ones((s,), bool).at[picked].set(False)
    out["dq_outside_is_zero"] = bool(jnp.all(got[1][0, rest] == 0))
    both = jax.jit(lambda q, k, v: (kernel(q, k, v), jax.grad(
        lambda q, k, v: jnp.sum(kernel(q, k, v).astype(jnp.float32) * w),
        argnums=(0, 1, 2))(q, k, v)))
    jax.block_until_ready(both(q, k, v))
    out["ms"] = kernel_ms(both, q, k, v, n=3, kernel_of=kernel_of)
    peak = peaks.peaks("TPU v5 lite")
    out["bound_ms"] = {
        name: 1e3 * counts.bound_seconds(*fn(1, h, s, d, g / h), peak)
        for name, fn in (("fwd", counts.flash_fwd), ("bwd", counts.flash_bwd))}
    out["ok"] = all(x <= BOUND for x in out["kernel"].values()) \
        and out["dq_outside_is_zero"]
    out["control_fails"] = any(
        not x <= BOUND for x in out["scale_of_sqrt_d"].values())
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash_chip_check.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if out["ok"] and out["control_fails"] else 1


if __name__ == "__main__":
    sys.exit(main())
