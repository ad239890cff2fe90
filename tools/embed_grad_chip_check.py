#!/usr/bin/env python3
"""python3 tools/embed_grad_chip_check.py [--seed n] [--widths]: the
embedding table's gradient ALONE, ON THE CHIP (any other backend exits 3
before anything is computed), in both forms `models/blocks.embed_rows`'
backward rule picks from, at the eleven training cells' (T rows of the
cotangent, V, D), bf16.

THE FORMS. `scatter`: `jnp.zeros((V, D)).at[tokens].add(d)`, what autodiff
makes of `table[tokens]` and what the rule keeps off a TPU, for a float32
table and over more than one device. `sorted`:
`ops/row_sums.sum_rows_by_index`, one sort of the T ids, one gather of the
rows into token order, one `tgmm` pass over tiles of 256 destinations (its
`[:V]` slice is a copy here, where nothing reads it; in a step it fuses
into the optimizer's read). `sorted_tiles`: the same with the gather as
`ops/row_moves.take_live_rows`' loop over row tiles, the form a share's
combine takes, where rows can be dead; here every row is live.

THE TOKENS. `uniform`: every id as likely, what the benchmark's cells draw;
`zipf`: id i with weight 1 / (i + 1), nearer to text: a few ids take most
rows, most ids none. The cotangent's values are eighths up to 8, so that a
float32 sum of them is exact in any order: `sorted` is compared with the
float32 scatter-add rounded once, bit for bit, and exit 1 on a difference;
`scatter_differs` counts the elements where the bf16 scatter-add, which
rounds after every row, is another number.

`--widths` runs, in place of the cells, both forms at T 8,192 and V 16,384
over row widths from 1,024 to 6,144: which D the scatter-add pays 1 us a row
at, which is what `ops/row_sums.sums_by_index_in_order` tells the widths
apart by.

`ms`: one call's BUSY time on the device's clock (`row_moves_chip_check`'s
`busy_ms`). Writes chiprun_out/embed_grad_chip_check.json (`--widths`:
embed_grad_widths.json).
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.ops import row_sums  # noqa: E402
from tools.row_moves_chip_check import busy_ms  # noqa: E402

# cell: (T, V, D) of a lookup, from the cell's traffic (per_chip_batch x
# seq; train-sdar-1chip looks up its noised and its clean copy at once) and
# its configuration (vocab_size, hidden_size). train-joyai-1chip and
# train-nemotron3-1chip look up twice a step (the MTP block); train-4chip's
# is a chip's rows of the batch, and its step keeps the scatter.
CELLS = {
    "train-1chip": (8192, 32768, 4096),
    "train-4chip": (4096, 32768, 4096),
    "train-olmoe-1chip": (8192, 50304, 2048),
    "train-joyai-1chip": (8192, 16160, 2048),
    "train-sdar-1chip": (16384, 18992, 2048),
    "train-ling-1chip": (8192, 19648, 2560),
    "train-nemotron3-1chip": (4096, 16384, 4096),
    "train-laguna-1chip": (8192, 12544, 2048),
    "train-smallthinker-1chip": (16384, 37984, 2560),
    "train-granite4-1chip": (32768, 100352, 2048),
    # 320 byte rows: ~102 rows of the cotangent collide in each (PR 57)
    "train-evabyte-1chip": (32768, 320, 4096),
}


def scatter(d, tokens, v):
    return jnp.zeros((v, d.shape[1]), d.dtype).at[tokens].add(d)


def sorted_tiles(d, tokens, v):
    return row_sums._sum_in_token_order(d, tokens, d.shape[0], v)


WIDTHS = {f"D {dim}": (8192, 16384, dim) for dim in (
    1024, 1536, 2048, 2560, 3072, 3584, 4096, 4608, 5120, 6144)}
FORMS = {"scatter": scatter, "sorted": row_sums.sum_rows_by_index,
         "sorted_tiles": sorted_tiles}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=56)
    ap.add_argument("--widths", action="store_true")
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print(f"embed_grad_chip_check: backend {jax.default_backend()!r}, "
              "not a TPU: run it through the chip tool", file=sys.stderr)
        return 3
    rng = np.random.default_rng(a.seed)
    out = {"device": jax.devices()[0].device_kind, "seed": a.seed, "ms": {},
           "sorted_ops_ms": {}, "scatter_differs": {}}
    wrong = []
    forms = {name: jax.jit(f, static_argnums=2) for name, f in FORMS.items()}
    for cell, (t, v, dim) in (WIDTHS if a.widths else CELLS).items():
        d = jnp.asarray(rng.integers(-64, 65, (t, dim)) / 8, jnp.bfloat16)
        weights = 1.0 / np.arange(1, v + 1)
        draws = {"uniform": rng.integers(0, v, t),
                 "zipf": rng.choice(v, t, p=weights / weights.sum())}
        for draw, ids in draws.items():
            tokens = jnp.asarray(ids, jnp.int32)
            want = jax.jit(lambda d, tok: scatter(
                d.astype(jnp.float32), tok, v).astype(d.dtype))(d, tokens)
            row = out["ms"].setdefault(cell, {}).setdefault(draw, {})
            for name, form in forms.items():
                got = jax.block_until_ready(form(d, tokens, v))
                ops = {} if (name, draw) == ("sorted", "uniform") else None
                row[name] = busy_ms(form, d, tokens, v, ops=ops)
                if ops:
                    out["sorted_ops_ms"][cell] = dict(sorted(
                        ops.items(), key=lambda kv: -kv[1])[:6])
                if name == "scatter":
                    out["scatter_differs"].setdefault(cell, {})[draw] = int(
                        jnp.sum(got != want))
                elif not bool(jnp.array_equal(got, want)):
                    wrong.append((cell, draw, name))
            print(f"{cell:26s} T {t:6d} V {v:6d} D {dim:5d} {draw:8s} "
                  + "  ".join(f"{k} {ms:7.3f} ms" for k, ms in row.items())
                  + f"  x{row['scatter'] / row['sorted']:.1f}", flush=True)
    out["wrong"] = wrong
    os.makedirs("chiprun_out", exist_ok=True)
    name = "embed_grad_widths" if a.widths else "embed_grad_chip_check"
    with open(f"chiprun_out/{name}.json", "w") as f:
        json.dump(out, f, indent=1)
    if wrong:
        print("a sorted sum differs from the float32 scatter-add rounded "
              f"once: {wrong}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
