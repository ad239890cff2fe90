#!/usr/bin/env python3
"""python3 tools/smallthinker_chip_check.py [--seed n]: the LOGITS of
`train-smallthinker-1chip`'s program against the plain reference's, ON THE
CHIP (any other backend exits 3 before anything is computed), at the cell's
own configuration and sequence (`benchmarks/configs/smallthinker-21ba3b-
train-1chip.json`, B 1 x S 16,384, seeded weights and ids): the last 512
positions of the one row, whose window layers see 4,096 keys and whose full
layer sees up to 16,384.

THE COMPARISON. `models/window_moe.forward_hidden` (bf16, the Pallas
kernels, the share's dispatch) times the head, against
`benchmarks/reference_smallthinker.hidden` (float32, `highest`) times the
head: `rms_rel` = ||got - want|| / ||want|| over [512, vocabulary rows],
`median_rel` the median over the 512 positions of the same ratio a position
(a position whose experts flipped moves by an expert's output, not by a
rounding: the median passes those by), `max_rel` = max |got - want| / max
|want|, and the share of (token, layer) pairs of ALL 16,384 positions whose
six chosen experts differ (a bf16 router input flips near-ties).

THE CONTROLS, each the reference itself with ONE thing wrong, which has to
read far over the tolerance: `full_as_window` (the full layer run under the
window), `window_without_rope` (the window layers run with no rotary
embedding), `router_reads_ffn_input` (the router after the attention), and
`program_float8`, the PROGRAM with every matrix rounded to float8_e4m3 (the
precision below the one the configuration states), against the reference on
the weights as they are. And `program_f32`: the program in float32 under `highest`, which says how much
of the first reading bf16 explains (at the cell's size the grouped matmul's
tiles, sized for bf16, do not fit VMEM in float32: then it says `refused`;
tests/test_window_moe_reference.py has the float32 program at 7e-8 of the
reference at toy widths).

Exit 1 if the program's `rms_rel` is over TOLERANCE or a control's under
it, or its `median_rel` over MEDIAN_TOLERANCE or an ATTENTION control's
under that (a router read elsewhere flips a few tokens' experts and leaves
the median position alone: `rms_rel` and the choices catch it).
Writes chiprun_out/pr50/smallthinker_chip_check.json.
"""
import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks import reference_smallthinker as ref  # noqa: E402
from ray_tpu.models import window_moe  # noqa: E402

# rms over the last 512 positions' logits, between the two sets of readings
# on the v5e (PERF.md section 6, PR 50): the program's 0.018 (bf16 rounds
# every product's operands to 2^-9, and 2% of the (token, layer) pairs
# choose another expert) and the controls' 0.049-0.059 (at seeded weights
# a sublayer adds to a residual stream that the embedding's unit rows
# dominate, so one layer run wrong moves the logits by a twentieth).
# The median position: the program's 0.0053 (no flip there: bf16 alone),
# the attention controls' 0.034 and 0.051
TOLERANCE = 3e-2
MEDIAN_TOLERANCE = 1.5e-2
LAST = 512


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=50)
    args = ap.parse_args()
    if jax.default_backend() != "tpu":
        print(f"backend {jax.default_backend()}: this check runs on the chip",
              file=sys.stderr)
        return 3
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "smallthinker-21ba3b-train-1chip.json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "pretrain-16k-b1.json")) as f:
        seq = json.load(f)["seq"]
    program = config["program"]
    fields = {f: config[k] for f, k in program["fields_from"].items()}
    fields.update(program["fields"])
    model = window_moe.WindowMoeConfig(**fields)
    key = jax.random.PRNGKey(args.seed)
    params = jax.jit(lambda k: window_moe.init(model, k))(key)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (1, seq), 0,
                                model.vocab_size)

    def program_logits(model, params):
        hidden, chosen = jax.jit(lambda p, t: window_moe.forward_hidden(
            p, t, model))(params, tokens)
        return jnp.einsum("sd,dv->sv", hidden[0, -LAST:],
                          params["lm_head"]).astype(jnp.float32), chosen

    def reference_logits(kinds=None, **wrong):
        with jax.default_matmul_precision("highest"):
            hidden, chosen = ref.hidden(params, tokens[0],
                                        dict(fields, **wrong), kinds)
            return hidden[-LAST:] @ params["lm_head"].astype(jnp.float32), \
                jnp.stack(chosen)

    def nll(logits):   # of the ids that follow, over the last positions
        logp = jax.nn.log_softmax(logits[:-1], -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[0, -LAST + 1:, None], -1))

    def distance(got, want):
        rows = jnp.linalg.norm(got - want, axis=-1) \
            / jnp.linalg.norm(want, axis=-1)
        return {"loss_rel_err": float(abs(nll(got) - nll(want)) / nll(want)),
                "rms_rel": float(jnp.linalg.norm(got - want)
                                 / jnp.linalg.norm(want)),
                "median_rel": float(jnp.median(rows)),
                "max_rel": float(jnp.max(jnp.abs(got - want))
                                 / jnp.max(jnp.abs(want)))}

    want, want_chosen = reference_logits()
    got, chosen = program_logits(model, params)
    out = {"seed": args.seed, "seq": seq, "positions": LAST,
           "tolerance": TOLERANCE, "median_tolerance": MEDIAN_TOLERANCE,
           "device": jax.devices()[0].device_kind,
           "program": distance(got, want)}
    same = jnp.all(jnp.sort(chosen, -1) == jnp.sort(want_chosen, -1), -1)
    out["program"]["choices_that_differ"] = float(1 - jnp.mean(same))
    kinds = ref.layer_kinds(fields)   # [(sliding, rope)] a published layer
    for name, wrong in (
            ("full_as_window", {"kinds": [(True, r) for _, r in kinds]}),
            ("window_without_rope", {"kinds": [(s, False) for s, _ in kinds]}),
            ("router_reads_ffn_input", {"router_input": "ffn_input"})):
        out[name] = distance(reference_logits(**wrong)[0], want)
    rounded = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.ndim >= 2 else a, params)
    out["program_float8"] = distance(program_logits(model, rounded)[0], want)
    try:
        f32 = dataclasses.replace(model, dtype=jnp.float32)
        with jax.default_matmul_precision("highest"):
            got32, chosen32 = program_logits(f32, jax.tree.map(
                lambda a: a.astype(jnp.float32), params))
        out["program_f32"] = distance(got32, want)
        out["program_f32"]["choices_that_differ"] = float(1 - jnp.mean(jnp.all(
            jnp.sort(chosen32, -1) == jnp.sort(want_chosen, -1), -1)))
    except Exception as e:  # noqa: BLE001 - a refusal is a finding here
        out["program_f32"] = {"refused": str(e)[:300]}
    controls = [out[n]["rms_rel"] for n in (
        "full_as_window", "window_without_rope", "router_reads_ffn_input",
        "program_float8")]
    medians = [out[n]["median_rel"] for n in (
        "full_as_window", "window_without_rope")]
    out["ok"] = bool(
        out["program"]["rms_rel"] <= TOLERANCE < min(controls)
        and out["program"]["median_rel"] <= MEDIAN_TOLERANCE < min(medians))
    print(json.dumps(out, indent=1))
    where = os.path.join(ROOT, "chiprun_out", "pr50")
    os.makedirs(where, exist_ok=True)
    with open(os.path.join(where, "smallthinker_chip_check.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
