#!/usr/bin/env python3
"""python3 tools/row_moves_chip_check.py [--seed n]: a share's two gathers
of rows ALONE, ON THE CHIP (any other backend exits 3 before anything is
computed), whole against `ops/row_moves.take_live_rows`' loop over live row
tiles, at the two buffers `ops/row_sums.py` is tuned for and three loads.

THE GATHERS. `take`: `parallel/moe._take_rows`, token rows [cap / 2, 2048]
-> the buffer [cap, 2048] in sorted order (16 held experts' groups, a
group's tokens ascending: what `sort_held` makes). `reorder`:
`ops/row_sums._sum_in_token_order`'s `rows[perm]`, the buffer -> itself in
token order (`perm` is the stable sort of `take`'s tokens, dead rows last).
bf16, cap 32,768 (train-sdar-1chip's first capacity) and 16,384
(train-joyai-1chip's); live rows 0.5, 1.0 and 1.9 x the even share cap / 2.

THE FORMS. `fill`: PR 44's `_take_rows`, the dead rows' index T and
`mode="fill"` (a `select` over [cap, D] after the gather); `whole`: the
dead rows' index clamped, one gather of `cap` rows (what `reorder` was);
`loop_<tile>`: `take_live_rows` at that row tile: each tile gathered, then
copied by one DMA into a buffer nothing has written; `loop_update_slice`:
the same at the program's tile with XLA's `dynamic_update_slice` for the
DMA; `loop_zeros_update_slice`: that into `jnp.zeros` (plain XLA throughout).
Every loop's live rows are compared with the whole gather's, bit for bit:
exit 1 on any difference.

`ms`: one call's BUSY time on the device's clock (the union of the `XLA
Ops` events of a short trace over the calls), so a loop's body, condition
and zero-fill are all in it. Writes chiprun_out/row_moves_chip_check.json.
"""
import argparse
import functools
import glob
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from ray_tpu.ops import row_moves  # noqa: E402
from ray_tpu.ops.row_moves import take_live_rows  # noqa: E402

D = 2048
CAPS = (32768, 16384)
LOADS = (0.5, 1.0, 1.9)  # and the even share + 1 row: one more tile
TILES = (1024, 2048, 4096, 8192)
GROUPS = 16


def indices(rng, cap, live):
    """-> (token [cap] with T = cap / 2 where dead, perm [cap]): `live`
    rows in GROUPS groups, a group's tokens distinct and ascending."""
    t = cap // 2
    bounds = np.linspace(0, live, GROUPS + 1).astype(int)
    token = np.full(cap, t, np.int32)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        token[lo:hi] = np.sort(rng.choice(t, hi - lo, replace=False))
    return token, np.argsort(token, kind="stable").astype(np.int32)


def busy_ms(fn, *args, n=10, ops=None):
    """`fn` (compiled already) traced over `n` calls -> the device's busy
    milliseconds a call; `ops`, a dict, is filled with each op's (a `while`
    holds its body's ops, which are events of their own)."""
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(n):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(
            d, "plugins", "profile", "*", "*.xplane.pb"))
        events = [
            (e.start_ns, e.start_ns + e.duration_ns, e.name)
            for p in ProfileData.from_file(path).planes
            if p.name.startswith("/device:TPU:")
            for ln in p.lines if ln.name == "XLA Ops" for e in ln.events]
    busy, end = 0.0, 0.0
    for lo, hi, name in sorted(events):
        busy += max(hi, end) - max(lo, end)
        end = max(hi, end)
        if ops is not None:
            name = name.split(" = ")[0].lstrip("%")
            ops[name] = ops.get(name, 0.0) + (hi - lo) * 1e-6 / n
    return busy * 1e-6 / n


def fill(x, index, live):
    return x.at[index].get(mode="fill", fill_value=0)


def whole(x, index, live):
    return x.at[jnp.minimum(index, x.shape[0] - 1)].get(
        mode="promise_in_bounds")


def loop(tile, x, index, live):
    return take_live_rows(x, jnp.minimum(index, x.shape[0] - 1), live, tile)


def with_xla(**parts):
    """`loop` at the program's tile with parts of `ops/row_moves.py` swapped
    for XLA's own: `_buffer=jnp.zeros`, `_placed=` a `dynamic_update_slice`."""
    def form(x, index, live):
        kept = {name: getattr(row_moves, name) for name in parts}
        for name, part in parts.items():
            setattr(row_moves, name, part)
        try:
            return loop(row_moves._ROW_TILE, x, index, live)
        finally:
            for name, part in kept.items():
                setattr(row_moves, name, part)
    return form


def update_slice(buf, rows, start):
    return jax.lax.dynamic_update_slice(buf, rows, (start, 0))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=45)
    a = ap.parse_args()
    if jax.default_backend() != "tpu":
        print(f"row_moves_chip_check: backend {jax.default_backend()!r}, not "
              "a TPU: run it through the chip tool", file=sys.stderr)
        return 3
    rng = np.random.default_rng(a.seed)
    forms = {"fill": fill, "whole": whole,
             "loop_update_slice": with_xla(_placed=update_slice),
             "loop_zeros_update_slice": with_xla(
                 _placed=update_slice, _buffer=jnp.zeros)}
    forms.update({f"loop_{t}": functools.partial(loop, t) for t in TILES})
    forms = {name: jax.jit(f) for name, f in forms.items()}
    out = {"device": jax.devices()[0].device_kind, "seed": a.seed, "ms": {},
           "ops_ms": {}}
    differ = []
    for cap in CAPS:
        key = jax.random.fold_in(jax.random.PRNGKey(a.seed), cap)
        sources = {
            "take": jax.random.normal(key, (cap // 2, D), jnp.bfloat16),
            "reorder": jax.random.normal(key, (cap, D), jnp.bfloat16)}
        for live in [int(load * cap // 2) for load in LOADS] + [cap // 2 + 1]:
            token, perm = indices(rng, cap, live)
            for gather, index in (("take", token), ("reorder", perm)):
                x, index = sources[gather], jnp.asarray(index)
                args = (x, index, jnp.int32(live))
                want = np.asarray(forms["whole"](*args))[:live].view(np.uint16)
                row = out["ms"].setdefault(
                    f"{gather}_{cap}", {}).setdefault(f"live_{live}", {})
                for name, f in forms.items():
                    if name == "fill" and gather == "reorder":
                        continue  # `rows[perm]` never filled
                    got = np.asarray(f(*args))[:live].view(np.uint16)
                    if not np.array_equal(got, want):
                        differ.append((gather, cap, live, name))
                    ops = {} if (cap, live) == (CAPS[0], CAPS[0] // 2) else None
                    row[name] = busy_ms(f, *args, ops=ops)
                    if ops:
                        out["ops_ms"][f"{gather}_{cap}_{name}"] = {
                            op: round(ms, 4) for op, ms in ops.items()}
                print(f"{gather}_{cap} live {live}: " + "  ".join(
                    f"{n} {ms:.3f}" for n, ms in row.items()), flush=True)
    out["differ"] = differ
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/row_moves_chip_check.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
