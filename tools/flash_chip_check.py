#!/usr/bin/env python3
"""python3 tools/flash_chip_check.py [--rule causal|window|eva --seq s
--seq-k t --heads h --kv-heads g --head-dim d --scale x --window w --chunk c
--seed n]: ONE flash call of `ops/flash_attention.py` alone ON THE CHIP (any
other backend exits 3) under the rule named, at the Granite cell's shape
unless told: causal, q `[1, 32768, 32, 64]`, k and v `[1, 32768, 8, 64]`,
scale 1 / 64. `--rule window --window w`: `SlidingWindow(w)`. `--rule eva
--window w --chunk c`: `ops/eva.eva_attention`, the pooling of the chunk
summaries and the call under `EvaWindows` over `--seq-k` = s + s / c keys
(the EvaByte cell: `--rule eva --heads 32 --kv-heads 32 --head-dim 128
--scale 0.08838834764831845 --window 2048 --chunk 16`), with the gradients
of phi and mu beside q's, k's and v's. `--seq-k` past `--seq` under the
other rules: the queries stand at the keys' end.

Against a plain oracle that such a sequence still fits: the cotangent of o is
zero outside `BLOCKS` blocks of 256 query rows (the first, one in the middle,
the last), so o is compared on those rows, dq on those rows (it is zero
elsewhere, which is checked too), the other gradients whole: the oracle is
float32 `jnp` softmax attention of each block against ALL keys under the
block's dense mask, written here from the positions (EVA's from
`benchmarks/reference_evabyte.py`'s `summaries`, `visible` and `attend`),
differentiated by XLA. Relative error in the Frobenius norm a tensor, bf16
operands on both sides; `BOUND` is what bf16 products with float32
accumulation keep. The controls that have to FAIL: the same oracle at
another scale (d ** -0.5, or half the scale where that IS the scale) and,
under `eva`, with the summaries left out and with the two kinds of key
normalised apart. `ms`: the three kernels on the device's clock from a short
trace, beside `bound_ms` (the rule's kept scores over the peak, or the
operands' bytes, by the opcount module that counts the rule). Writes
chiprun_out/flash_chip_check.json.

`--sweep`: no oracle; the same call at several sizes of a loop plan's bodies
(`ops/flash_attention._LOOP_BODY`, set here for the process: no body, which
is one step an iteration, then bodies of 2, of 4 and of 8 of a row's whole
tiles, then the ladder 4, 2; or `--bodies '4,2;4,2,1'`: those ladders in
turn), ms a kernel, the steps that run in bodies, and the largest relative
error of o and the gradients against the first form's. (Bodies of ANY steps
under the plan's mask, which PR 60 measured and did not keep, are not a form
the program has.) What `_LOOP_BODY`'s comment was read from
(PERF.md §6, PR 60), at the four cells' loop calls: the default (Granite's);
`--seq 8192 --heads 48 --kv-heads 8 --head-dim 128 --scale 0.08838834764831845`
(Laguna's full layers); `--seq 16384 --heads 28 --kv-heads 4 --head-dim 128
--scale 0.08838834764831845` (SmallThinker's, and with `--rule window --window
4096` its window layers' edge rows); EvaByte's above. Appends a line to
chiprun_out/flash_loop_body_sweep.jsonl.

`--ops`: no oracle either; the call's BACKWARD PASS alone as one program (its
residuals made by another), traced: the two kernels' ms and, by name and
shape, the device ms a call of EVERY other op of it, largest first (what
makes delta, what is left of the relayouts, the sums over a KV head's group,
the transposes): "the call alone, before | after" for a change to what feeds
the kernels. It takes `--batch`, `--rule bd --block n` (`BlockDiffusion(s /
2, n)`) and `--rope r` (a call in parts: r rotary channels and ONE rotary
key) besides, so it runs at any cell's shape: SDAR's `--ops --rule bd --batch
4 --seq 4096 --block 4 --heads 32 --kv-heads 4 --head-dim 128 --scale
0.08838834764831845`, JoyAI's `--ops --batch 4 --seq 2048 --heads 32
--kv-heads 32 --head-dim 128 --rope 64 --scale 0.07216878364870323`,
Granite's `--ops`. Appends a line to chiprun_out/flash_backward_ops.jsonl.
"""
import argparse
import importlib
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import eva  # noqa: E402
from ray_tpu.ops.flash_attention import (  # noqa: E402
    BlockDiffusion, EvaWindows, SlidingWindow, block_schedule,
    flash_attention)
from tools.kda_chip_check import device_events, kernel_ms  # noqa: E402

BOUND = 2e-2
ROWS = 256


def attend(scores, kept, values):
    return jax.nn.softmax(jnp.where(kept, scores, -jnp.inf), -1) @ values


def oracle(q, k, v, blocks, scale, keep, attend=attend):
    """-> o of the query rows of `blocks` [len(blocks) * ROWS, H, D],
    float32: `attend`(scale q k^T, keep(rows), v) a block and head (one
    traced body, mapped over the blocks and the heads), `keep` the block's
    dense mask [ROWS, keys] from its rows' indices."""
    rep = q.shape[2] // k.shape[2]
    q, k, v = (jnp.moveaxis(x[0].astype(jnp.float32), 1, 0)
               for x in (q, k, v))                       # [heads, S, D]
    k, v = (jnp.repeat(x, rep, axis=0) for x in (k, v))

    def block(blk):
        rows = blk * ROWS + jnp.arange(ROWS)
        kept = keep(rows)
        # a head's [ROWS, keys] scores are formed again in the backward
        # pass, not kept for every block and head (13 GB at 34,816 keys)
        return jax.lax.map(jax.checkpoint(
            lambda qkv: attend((qkv[0][rows] @ qkv[1].T) * scale, kept,
                               qkv[2])), (q, k, v))      # [heads, ROWS, D]

    with jax.default_matmul_precision("highest"):
        out = jax.lax.map(block, jnp.asarray(blocks))
    return jnp.moveaxis(out, 1, 2).reshape(-1, q.shape[0], q.shape[2])


def eva_oracle(q, k, v, phi, mu, blocks, scale, window, chunk, **how):
    """The blocks' rows of EVA attention by the plain reference's pieces: an
    explicit softmax a chunk, then the dense mask over [summaries ; bytes]."""
    from benchmarks import reference_evabyte as ref

    f32 = jnp.float32
    s = q.shape[1]
    with jax.default_matmul_precision("highest"):
        k_sum, v_sum = ref.summaries(k[0].astype(f32), v[0].astype(f32),
                                     phi.astype(f32), mu.astype(f32), chunk)
    return oracle(
        q, jnp.concatenate([k_sum, k[0].astype(f32)])[None],
        jnp.concatenate([v_sum, v[0].astype(f32)])[None], blocks, scale,
        lambda rows: jnp.concatenate(
            ref.visible(rows[:, None], s, window, chunk), -1), **how)


def kernel_of(event_name):
    m = re.match(r"%[\w.\-]+ = (\(.*?\)|\w+\[[\d,]*\]\S*) custom-call\(",
                 event_name)
    if not m or "tpu_custom_call" not in event_name:
        return None
    outputs = re.findall(r"(\w+)\[", m[1])
    return {("bf16", "f32"): "fwd_ms", ("bf16",): "dq_ms",
            ("bf16", "bf16"): "dkv_ms"}.get(tuple(outputs))


def value_and_grads(kernel, w, n):
    """jit of (o, the gradients of sum(o * w) by the first `n` operands)."""
    return jax.jit(lambda *x: (kernel(*x), jax.grad(
        lambda *x: jnp.sum(kernel(*x).astype(jnp.float32) * w),
        argnums=tuple(range(n)))(*x)))


def backward_ops(kernel, args, w, n=3):
    """-> the ms a call, on the device's clock, of the ops of `kernel`'s
    backward pass alone: {"dq_ms", "dkv_ms"} and [(ms, op and shape)] of
    every other op, largest first; and each gradient's Frobenius norm (two
    commits' runs at one seed give the same inputs: norms to compare)."""
    o, pull = jax.jit(lambda *x: jax.vjp(kernel, *x))(*args)
    back = jax.jit(lambda pull, g: pull(g))
    g = w.astype(o.dtype)
    norms = [float(jnp.linalg.norm(x.astype(jnp.float32)))
             for x in jax.block_until_ready(back(pull, g))]
    kernels, others = {}, {}
    for name, ns in device_events(back, pull, g, n=n):
        kernel = kernel_of(name)
        m = re.match(r"%([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(", name)
        op = kernel or (
            f"{m[1]} {m[2]}" + (" " + m[3] if m[3] != "fusion" else "")
            if m else name)[:160]
        into = kernels if kernel else others
        into[op] = into.get(op, 0.0) + ns * 1e-6 / n
    return kernels, sorted(((ms, op) for op, ms in others.items()),
                           reverse=True), norms


FORMS = [(), (2,), (4,), (8,), (4, 2)]


def sweep(kernel, args, w, rule, s, s_k, forms):
    """-> a record a form of `forms`: the three kernels' ms, the steps of
    the forward's and dk/dv's plans that run in bodies, and how far o and
    the gradients are from the first form's."""
    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    out, first = [], None
    for form in forms:
        flash._LOOP_BODY = {"fwd": form, "dkv": form}
        flash._block_schedule.cache_clear()
        plans = block_schedule(s, s_k, 512, 512, rule)
        both = value_and_grads(kernel, w, len(args))
        o, grads = jax.block_until_ready(both(*args))
        got = [x.astype(jnp.float32) for x in (o,) + tuple(grads)]
        first = first or got
        out.append({
            "body": form,
            "ms": kernel_ms(both, *args, n=3, kernel_of=kernel_of),
            "steps_loop_body": [plans[name].steps_loop_body
                                for name in ("fwd", "dkv")],
            "steps": [len(plans[name].tiles) for name in ("fwd", "dkv")],
            "rel_err_to_first": max(
                float(jnp.linalg.norm(x - y) / jnp.linalg.norm(y))
                for x, y in zip(got, first))})
        print(json.dumps(out[-1]), flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--bodies", default=None, help="with --sweep: the sizes "
                    "of a loop's bodies in place of the default forms, as "
                    "'4,2;2,1'")
    ap.add_argument("--ops", action="store_true")
    ap.add_argument("--batch", type=int, default=1, help="with --ops")
    ap.add_argument("--block", type=int, default=4, help="--rule bd's")
    ap.add_argument("--rope", type=int, default=0, help="with --ops: the "
                    "rotary part's width of a call in parts")
    ap.add_argument("--seed", type=int, default=55)
    ap.add_argument("--rule", choices=("causal", "window", "eva", "bd"),
                    default="causal")
    ap.add_argument("--seq", type=int, default=32768)
    ap.add_argument("--seq-k", type=int, default=None)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--scale", type=float, default=0.015625)
    ap.add_argument("--window", type=int, default=2048)
    ap.add_argument("--chunk", type=int, default=16)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print(f"flash_chip_check: backend {jax.default_backend()!r}, not a "
              "TPU: run it through the chip tool", file=sys.stderr)
        return 3
    from benchmarks import opcount_evabyte, opcount_granite4, opcount_laguna
    from benchmarks import peaks

    s, h, g, d = a.seq, a.heads, a.kv_heads, a.head_dim
    is_eva = a.rule == "eva"
    s_k = s + s // a.chunk if is_eva else a.seq_k or s
    if a.seq_k not in (None, s_k):
        ap.error(f"--rule eva has {s_k} keys")
    n = s // ROWS
    blocks = sorted({0, n // 2, n - 1})
    ks = jax.random.split(jax.random.PRNGKey(a.seed), 6)
    bf16 = jnp.bfloat16
    # keys of RMS 1 / (scale sqrt d): the scores have RMS ~1
    rows_k = s if is_eva else s_k
    q = jax.random.normal(ks[0], (1, s, h, d)).astype(bf16)
    k = (jax.random.normal(ks[1], (1, rows_k, g, d))
         / (a.scale * d ** 0.5)).astype(bf16)
    v = jax.random.normal(ks[2], (1, rows_k, g, d)).astype(bf16)
    args, names = (q, k, v), ("o", "dq", "dk", "dv")
    if is_eva:  # pooling logits of RMS ~1 too; an offset of the keys' size
        phi = (jax.random.normal(ks[4], (g, d)) / d ** 0.5).astype(bf16)
        mu = jax.random.normal(ks[5], (g, d)).astype(bf16)
        args, names = args + (phi, mu), names + ("dphi", "dmu")
    picked = jnp.concatenate(
        [jnp.arange(b * ROWS, (b + 1) * ROWS) for b in blocks])
    w_rows = jax.random.normal(ks[3], (len(picked), h, d))
    w = jnp.zeros((1, s, h, d)).at[0, picked].set(w_rows)
    at_end = jnp.arange(s_k)[None] - (s_k - s)   # a key's index as a query's

    if is_eva:
        def kernel(*x):
            return eva.eva_attention(*x, a.window, a.chunk, scale=a.scale,
                                     use_pallas=True)

        def plain(scale, **how):
            return lambda *x: eva_oracle(*x, blocks, scale, a.window,
                                         a.chunk, **how)
    else:
        rule = SlidingWindow(a.window) if a.rule == "window" else True

        def kernel(*x):
            return flash_attention(*x, causal=rule, scale=a.scale,
                                   use_pallas=True)

        def keep(rows):
            kept = rows[:, None] >= at_end
            if a.rule == "window":
                kept &= rows[:, None] - at_end < a.window
            return kept

        def plain(scale, **how):
            return lambda *x: oracle(*x, blocks, scale, keep, **how)

    if a.rule == "bd" and not a.ops:
        ap.error("--rule bd has no oracle here: with --ops")
    if a.ops:
        if is_eva:
            ap.error("--ops runs flash_attention's own rules")
        rule = {"causal": True, "window": SlidingWindow(a.window),
                "bd": BlockDiffusion(s // 2, a.block)}[a.rule]
        args = tuple(jnp.broadcast_to(x, (a.batch,) + x.shape[1:])
                     for x in (q, k, v))
        if a.rope:
            args += (jax.random.normal(ks[4], (a.batch, s, h, a.rope))
                     .astype(bf16),
                     jax.random.normal(ks[5], (a.batch, s_k, 1, a.rope))
                     .astype(bf16))

        def call(q, k, v, *parts):
            return flash_attention(
                q, k, v, causal=rule, scale=a.scale, use_pallas=True,
                **dict(zip(("q_rope", "k_rope"), parts)))

        kernels, others, norms = backward_ops(
            call, args, jax.random.normal(ks[3], args[0].shape))
        out = {"device": jax.devices()[0].device_kind, "seed": a.seed,
               "rule": a.rule, "shape": [a.batch, s, h, d], "keys": s_k,
               "kv_heads": g, "rope": a.rope, "grad_norms": norms,
               "ms": kernels,
               "other_ops_ms": sum(ms for ms, _ in others),
               "other_ops": [[round(ms, 4), op] for ms, op in others]}
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/flash_backward_ops.jsonl", "a") as f:
            f.write(json.dumps(out) + "\n")
        print(json.dumps(out, indent=1))
        return 0
    if a.sweep:
        rule = EvaWindows(s, a.window, a.chunk) if is_eva \
            else SlidingWindow(a.window) if a.rule == "window" else True
        out = {"device": jax.devices()[0].device_kind, "seed": a.seed,
               "rule": a.rule, "shape": [1, s, h, d], "keys": s_k,
               "kv_heads": g, "forms": sweep(
                   kernel, args, w, rule, s, s_k, FORMS if not a.bodies else [
                       tuple(map(int, sizes.split(",")))
                       for sizes in a.bodies.split(";")])}
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/flash_loop_body_sweep.jsonl", "a") as f:
            f.write(json.dumps(out) + "\n")
        return 0
    every = tuple(range(len(args)))

    def run(fn, loss):
        return (jax.jit(fn)(*args),) + jax.jit(
            jax.grad(loss, argnums=every))(*args)

    got = run(lambda *x: kernel(*x)[0, picked],
              lambda *x: jnp.sum(kernel(*x).astype(jnp.float32) * w))

    def against(fn):
        want = run(fn, lambda *x: jnp.sum(fn(*x) * w_rows))
        rel = lambda x, y: float(  # noqa: E731
            jnp.linalg.norm(x.astype(jnp.float32) - y.astype(jnp.float32))
            / jnp.linalg.norm(y.astype(jnp.float32)))
        return {name: rel(x, y) for name, x, y in zip(names, got, want)}

    other = d ** -0.5 if abs(a.scale - d ** -0.5) > 1e-6 else a.scale / 2
    controls = {"another_scale": plain(other)}
    if is_eva:
        n_sum = s // a.chunk

        def apart(scores, kept, values):
            out = 0.0
            for part in (slice(0, n_sum), slice(n_sum, None)):
                probs = jax.nn.softmax(jnp.where(
                    kept[:, part], scores[:, part], -1e30), -1)
                out = out + jnp.where(
                    jnp.any(kept[:, part], -1, keepdims=True),
                    probs @ values[part], 0.0)
            return out

        controls["no_summaries"] = plain(a.scale, attend=lambda sc, kept, vals:
                                         attend(sc, kept.at[:, :n_sum].set(
                                             False), vals))
        controls["normalised_apart"] = plain(a.scale, attend=apart)
    out = {"device": jax.devices()[0].device_kind, "seed": a.seed,
           "rule": a.rule, "shape": [1, s, h, d], "keys": s_k, "kv_heads": g,
           "scale": a.scale, "bound": BOUND, "rows_compared": len(picked),
           "kernel": against(plain(a.scale)),
           "controls": {name: against(fn) for name, fn in controls.items()}}
    rest = jnp.ones((s,), bool).at[picked].set(False)
    out["dq_outside_is_zero"] = bool(jnp.all(got[1][0, rest] == 0))
    both = value_and_grads(kernel, w, len(args))
    jax.block_until_ready(both(*args))
    out["ms"] = kernel_ms(both, *args, n=3, kernel_of=kernel_of)
    peak = peaks.peaks("TPU v5 lite")
    if is_eva:
        counted = {"fwd": opcount_evabyte.eva_flash_fwd(
            1, h, s, d, a.window, a.chunk), "bwd": opcount_evabyte.
            eva_flash_bwd(1, h, s, d, a.window, a.chunk)}
    elif a.rule == "window":
        counted = {"fwd": opcount_laguna.swa_flash_fwd(
            1, h, s, d, a.window, g / h), "bwd": opcount_laguna.swa_flash_bwd(
                1, h, s, d, a.window, g / h)}
    else:
        counted = {"fwd": opcount_granite4.flash_fwd(1, h, s, d, g / h),
                   "bwd": opcount_granite4.flash_bwd(1, h, s, d, g / h)}
    out["bound_ms"] = {name: 1e3 * opcount_granite4.bound_seconds(*c, peak)
                       for name, c in counted.items()}
    out["ok"] = all(x <= BOUND for x in out["kernel"].values()) \
        and out["dq_outside_is_zero"]
    out["control_fails"] = all(
        any(not x <= BOUND for x in errs.values())
        for errs in out["controls"].values())
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash_chip_check.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0 if out["ok"] and out["control_fails"] else 1


if __name__ == "__main__":
    sys.exit(main())
