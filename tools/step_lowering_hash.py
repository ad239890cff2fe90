"""sha256 of every training cell's lowered train step, with no chip: the
proof a refactor of `ray_tpu/models/` (or of anything the step traces) gives
that it moved nothing. Run it on the parent commit and on the change, from a
checkout, and compare the two tables:

    JAX_PLATFORMS=cpu python3 tools/step_lowering_hash.py --out DIR
    JAX_PLATFORMS=cpu python3 tools/step_lowering_hash.py --against DIR

Each cell of `BENCHMARK.json` whose configuration is of a `train` kind
(`train`, or a kind that runs `train_cell`'s step after a comparison of its
own: `train_hc`, `train_kda`) is built as `benchmarks/train_cell.py` builds it (the `program` group at the
published widths, the traffic's B x S, AdamW, `train.make_train_step` with
the state donated), on a DESCRIBED v5e (`jax.experimental.topologies`, as
`tests/test_tpu_aot_compile.py`: no device is touched, no array is made)
and lowered, not compiled: `.lower(...).as_text()`. `jax.default_backend`
is made to say "tpu" for the run, so every kernel takes its Pallas branch
as it does on the chip. A Pallas call's payload (serialised MLIR whose
locations name the files and lines of every frame that led to the call, the
model modules' too) is hashed as its assembly WITHOUT locations: the kernel
itself counts, where it was called from does not (`canonical`). One process
a cell (`--cell`), so a cell's counters and caches are its own. `--out`
keeps each text beside the table, for a diff where a hash moves (`--rehash
DIR` makes the table again from kept texts); `--tiny` lowers the module's
`tiny()` configuration at B 2 x S 64 on the CPU instead (seconds, for
iterating). `--cell NAME --memory` compiles that one step too and prints the
bytes the compiler places (arguments, outputs, aliased, temporaries): whether
a change to what the step holds still fits the chip, with no chip;
`--over '{"field": value}'` sets fields of the program's config over the
file's first: whether the next larger share or depth would fit too; with
`--out DIR` it also keeps `<cell>.ops.json`, the compiled step's ops as the
device trace will name them (`event_ops`) and the counters the lowering
left: what a trace query would take, with no chip.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import json
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = []
    for w in bench["workloads"]:
        with open(os.path.join(ROOT, "benchmarks", "configs",
                               w["config"] + ".json")) as f:
            config = json.load(f)
        if config["kind"].startswith("train"):
            out.append((w, config))
    return out


def canonical(text: str) -> str:
    """The lowered text with every Pallas payload replaced by the sha256 of
    the kernel's assembly printed without locations."""
    from jax._src.lib.mlir import ir

    def kernel(match):
        config = json.loads(re.sub(
            r"\\([0-9A-Fa-f]{2})", lambda m: chr(int(m[1], 16)), match[1]))
        call = config["custom_call_config"]
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            call["body"] = hashlib.sha256(ir.Module.parse(base64.b64decode(
                call["body"])).operation.get_asm(
                    enable_debug_info=False).encode()).hexdigest()
        return "backend_config = " + json.dumps(config, sort_keys=True)

    return re.sub(r'backend_config = "((?:[^"\\]|\\.)*custom_call_config'
                  r'(?:[^"\\]|\\.)*)"', kernel, text)


def event_ops(compiled_text: str) -> list:
    """A compiled step's text -> [[op, scope]]: the ops that run as events
    of their own (every instruction outside a fused computation's body), as
    the device trace names them (the text up to the operands; a Pallas
    call's operands dropped), with the scope the op's metadata keeps: what
    a metric's trace query is run over, with no chip."""
    ops, fused = [], False
    for line in compiled_text.splitlines():
        if re.match(r"^%?fused_computation|^%?\S*fused\S* \(", line):
            fused = True
        elif line.startswith("}"):
            fused = False
        elif not fused:
            m = re.match(r"^\s*(?:ROOT )?(%[\w.\-]+ = \(?\w+\[[\d,]*\]\S* "
                         r"(?:\S+ )*?[\w\-]+\()", line)
            if m and not any(f" {kind}(" in line for kind in (
                    "parameter", "constant", "get-tuple-element", "bitcast",
                    "tuple")):
                scope = re.search(r'op_name="([^"]*)"', line)
                text = line.strip()
                ops.append([
                    text[:400] if "tpu_custom_call" not in text
                    else re.sub(r"custom-call\(.*", "custom-call(%a), "
                                'custom_call_target="tpu_custom_call"', text),
                    scope.group(1) if scope else ""])
    return ops


def digest(text: str) -> str:
    return hashlib.sha256(canonical(text).encode()).hexdigest()


def lowered_step(workload, config, tiny: bool, over=None):
    """-> the cell's train step, lowered (`jax.stages.Lowered`)."""
    import importlib
    from functools import partial

    import jax
    import jax.numpy as jnp
    import optax
    from jax.experimental import topologies

    from ray_tpu import train
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.parallel.sharding import (
        LogicalAxisRules, logical_sharding, param_shardings)
    from ray_tpu.train.step import TrainState

    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           workload["traffic"] + ".json")) as f:
        traffic = json.load(f)
    program = config["program"]
    module = importlib.import_module(program["module"])
    config_class = getattr(module, program["config_class"])
    rules = LogicalAxisRules()
    if tiny:
        model = config_class.tiny()
        mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
        batch, seq = 2, 64
    else:
        jax.default_backend = lambda: "tpu"
        fields = {k: config[v] for k, v in program["fields_from"].items()}
        fields.update(program["fields"])
        fields.update(over or {})
        model = config_class(**fields)
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
        mesh = build_mesh(MeshConfig(**config["mesh"]),
                          devices=topo.devices[:workload["chips"]])
        batch = traffic["per_chip_batch"] * workload["chips"]
        seq = traffic["seq"]
    t = config["trainer"]
    opt = optax.adamw(t["learning_rate"], weight_decay=t["weight_decay"])
    # `train.init_train_state`'s shardings, with no array made
    params = jax.eval_shape(partial(module.init, model),
                            jax.random.PRNGKey(0))
    p_sh = param_shardings(module.param_logical_axes(model), mesh, rules)
    replicated = logical_sharding(mesh, (), rules)
    p_treedef = jax.tree.structure(params)

    def map_opt(node):
        if jax.tree.structure(node) == p_treedef:
            return p_sh
        one_level = jax.tree_util.default_registry.flatten_one_level(node)
        if one_level is None:
            return replicated
        treedef = jax.tree.structure(node, is_leaf=lambda x: x is not node)
        return jax.tree.unflatten(treedef, [map_opt(c) for c in one_level[0]])

    opt_state = jax.eval_shape(opt.init, params)
    shardings = TrainState(params=p_sh, opt_state=map_opt(opt_state),
                           step=replicated)
    abstract = lambda tree, sh: jax.tree.map(  # noqa: E731
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sh)
    state = TrainState(
        params=abstract(params, p_sh),
        opt_state=abstract(opt_state, shardings.opt_state),
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated))
    bs = train.batch_sharding(mesh, rules)
    step = train.make_train_step(
        partial(module.loss_fn, config=model, mesh=mesh, rules=rules), opt,
        shardings, batch_sharding={"inputs": bs, "targets": bs})
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=bs)
    return step.lower(state, {"inputs": tokens, "targets": tokens})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", help="one cell, in this process")
    ap.add_argument("--out", help="write hashes.json and each text here")
    ap.add_argument("--against", help="a directory --out wrote: compare")
    ap.add_argument("--rehash", help="a directory of kept texts: its table")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--over", type=json.loads, help="with --cell: fields of "
                    "the program's config, as JSON, over the file's")
    ap.add_argument("--memory", action="store_true", help="with --cell: "
                    "COMPILE the step for the described v5e and print what "
                    "the compiler places (a refusal is its error)")
    args = ap.parse_args()
    sys.path.insert(0, ROOT)
    if args.rehash:
        hashes = {}
        for name in sorted(os.listdir(args.rehash)):
            if name.endswith(".mlir"):
                with open(os.path.join(args.rehash, name)) as f:
                    hashes[name[:-5]] = digest(f.read())
        with open(os.path.join(args.rehash, "hashes.json"), "w") as f:
            json.dump(hashes, f, indent=1)
        print(json.dumps(hashes, indent=1))
        return 0
    if args.cell:
        (found,) = [c for c in cells() if c[0]["name"] == args.cell]
        lowered = lowered_step(*found, args.tiny, args.over)
        if args.memory:
            compiled = lowered.compile()
            memory = compiled.memory_analysis()
            print("MEMORY " + json.dumps({
                name: getattr(memory, name + "_size_in_bytes")
                for name in ("argument", "output", "alias", "temp")}))
            if args.out:
                from ray_tpu._private import device_profiler

                with open(os.path.join(args.out, args.cell + ".ops.json"),
                          "w") as f:
                    json.dump({
                        "ops": event_ops(compiled.as_text()),
                        "counters": device_profiler.snapshot()["counters"]},
                        f)
            return 0
        text = lowered.as_text()
        if args.out:
            with open(os.path.join(args.out, args.cell + ".mlir"), "w") as f:
                f.write(text)
        print("HASH " + digest(text))
        return 0
    if args.out:
        os.makedirs(args.out, exist_ok=True)
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled")
    hashes = {}
    for workload, _ in cells():
        cmd = [sys.executable, os.path.abspath(__file__), "--cell",
               workload["name"]] + (["--tiny"] if args.tiny else []) \
            + (["--out", args.out] if args.out else [])
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True)
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("HASH ")]
        hashes[workload["name"]] = lines[0][5:] if lines \
            else "FAILED: " + proc.stderr[-600:]
        print(workload["name"], hashes[workload["name"]], flush=True)
    if args.out:
        with open(os.path.join(args.out, "hashes.json"), "w") as f:
            json.dump(hashes, f, indent=1)
    if args.against:
        with open(os.path.join(args.against, "hashes.json")) as f:
            before = json.load(f)
        moved = [c for c in hashes if hashes[c] != before.get(c)]
        print("moved:", moved or "none")
        return 1 if moved else 0
    return 0 if not any(h.startswith("FAILED") for h in hashes.values()) \
        else 1


if __name__ == "__main__":
    sys.exit(main())
