"""How a model whose layers repeat in periods runs the ones it holds: the
plan as data (`segments`: which held layers fill whole periods, which run
unrolled, in what order) and its ONE executor (`walk`: a `lax.scan` over the
stacked whole periods, the others unrolled, the chosen experts gathered in
layer order), which `hybrid_moe`, `window_moe`, `granite_hybrid` and
`nemotron_h` all run. A model says what its layers ARE (`body`), where
their parameters sit and what a period is made of; nothing here knows a
model."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu._private import device_profiler


def held_layers(layers, n: int):
    """The published indices a program holds, in order: `layers`, or all
    `n` where None; refused where they are none, out of order, repeated or
    outside [0, n)."""
    held = tuple(range(n)) if layers is None else tuple(layers)
    if list(held) != sorted(set(held)) or not held \
            or not 0 <= held[0] <= held[-1] < n:
        raise ValueError(f"layers {held} of {n}")
    return held


def segments(held, n_dense: int, period: int, start: int = 0):
    """-> (dense, loose, periods, segments): the held layers' published
    indices `held` (in order), split into the dense ones (below `n_dense`),
    the layers that run unrolled and the first indices of the whole ALIGNED
    periods (`period` consecutive held layers from an index = `start` mod
    `period` on, none of them dense), each in order; and the execution
    order as segments ("dense", n), ("loose", n), ("periods", n) of
    consecutive layers / periods."""
    dense = [i for i in held if i < n_dense]
    rest = [i for i in held if i >= n_dense]
    have, loose, periods, out = set(rest), [], [], []

    def add(kind):
        if out and out[-1][0] == kind:
            out[-1][1] += 1
        else:
            out.append([kind, 1])

    for _ in dense:
        add("dense")
    i = 0
    while i < len(rest):
        first = rest[i]
        if (first - start) % period == 0 and all(
                first + j in have for j in range(period)):
            periods.append(first)
            add("periods")
            i += period
        else:
            loose.append(first)
            add("loose")
            i += 1
    return dense, loose, periods, [tuple(s) for s in out]


def at(tree, i):
    """Layer i of a stack: row i of every leaf."""
    return jax.tree.map(lambda a: a[i], tree)


def walk(x, segments, unrolled, stacks, periods, runs, body):
    """x [B, S, D] through the layers a plan holds, in its order -> (x, the
    chosen experts [L, T, k] of every layer that routes, in the layers'
    order, or None where none does).

    `body`: kind -> the layer of that kind, (x, ONE layer's parameters) ->
    (x, the layer's chosen experts [T, k] or None); asked once a layer
    unrolled and once a run of a period. `segments`: [(name, n)], as
    `segments` gives them. ("periods", n): the next n whole periods, one
    `lax.scan` over `periods`, {kind: that kind's parameters, every leaf
    leading with the period}. Any other name: n layers unrolled, each the
    next entry of `unrolled` (a kind a layer, in execution order) on the
    next layer of `stacks[kind]` (leaves leading with the kind's unrolled
    layers). `runs`: a period as runs of one kind, [(kind, n)]: the next n
    layers of the kind's stack INSIDE a period (leaves [periods, the kind's
    layers a period, ...]), scanned where n > 1; (kind, None): the kind's
    one layer a period, whose leaves carry no such dim. Counted per
    lowering: `pattern.periods`, `pattern.layers_unrolled`."""
    def period(x, p):
        taken, chosen = {}, []
        for kind, n in runs:
            if n is None:
                x, e = body(kind)(x, p[kind])
            else:
                first = taken.get(kind, 0)
                taken[kind] = first + n
                if n == 1:
                    x, e = body(kind)(x, at(p[kind], first))
                else:
                    x, e = jax.lax.scan(body(kind), x, jax.tree.map(
                        lambda a: a[first:first + n], p[kind]))
            if e is not None:
                chosen.append((e, n is not None and n > 1))
        if len(chosen) == 1 and not chosen[0][1]:
            return x, chosen[0][0]  # one routing layer a period: [T, k]
        return x, jnp.concatenate([e if scanned else e[None]
                                   for e, scanned in chosen]) \
            if chosen else None

    unrolled, taken, chosen = iter(unrolled), {}, []
    for name, n in segments:
        if name == "periods":
            first = taken.get(name, 0)
            taken[name] = first + n
            x, e = jax.lax.scan(period, x, jax.tree.map(
                lambda a: a[first:first + n], periods))
            if e is not None:  # [n, T, k], or [n, layers a period, T, k]
                chosen.append(e if e.ndim == 3
                              else e.reshape((-1,) + e.shape[2:]))
            device_profiler.count("pattern.periods", n)  # per lowering
            continue
        for _ in range(n):
            kind = next(unrolled)
            first = taken.get(kind, 0)
            taken[kind] = first + 1
            x, e = body(kind)(x, at(stacks[kind], first))
            if e is not None:
                chosen.append(e[None])
        device_profiler.count("pattern.layers_unrolled", n)
    return x, jnp.concatenate(chosen) if chosen else None
