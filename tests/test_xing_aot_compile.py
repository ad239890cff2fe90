"""`train-xing4-1chip` as the v5e's compiler sees it, with no chip
(`jax.experimental.topologies`, as tests/test_granite_aot_compile.py): the
whole train step at the published widths, from the configuration file, is
PLACED on one chip's HBM under remat "residuals"; its Pallas calls are the
flash kernels and the grouped matmuls the MLA and share cells have and,
since PR 62, the residual path's four (`ops/stream_mix.py`, named by their
scope: `%hc.pre.3`, `%hc.post.1`); and every trace query the cell is listed
under, run over the compiled step's op names (what the device trace names
its events by), takes the ops it is for and no other layer's, told by the
scope the compiler keeps in an op's metadata; what `hc_time_share`'s
by-shape query takes of the path's XLA remnants is held as a number."""

import json
import math
import os
import re
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "train-xing4-1chip"

_SCRIPT = r"""
import json
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.experimental import topologies

from ray_tpu import train
from ray_tpu._private import device_profiler
from ray_tpu.models import blocks, mla_moe
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.parallel.sharding import (
    LogicalAxisRules, logical_sharding, param_shardings)

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1), devices=topo.devices[:1])
rules = LogicalAxisRules()
one_chip = logical_sharding(mesh, (), rules)
out = {"device_kind": topo.devices[0].device_kind}


def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# every choice of a kernel follows jax.default_backend(), cpu here: the flash
# call's, the grouped matmuls', the row moves', the sorted sums' (a share's
# combine, the embedding's gradient). The step compiled is the chip's
jax.default_backend = lambda: "tpu"
blocks.flash_attention = partial(flash_attention, use_pallas=True)

with open(CONFIG) as f:
    config = json.load(f)
program = config["program"]
fields = {k: config[v] for k, v in program["fields_from"].items()}
fields.update(program["fields"])
cfg = mla_moe.MlaMoeConfig(**fields)
opt = optax.adamw(1e-4, weight_decay=0.0)
params = jax.eval_shape(partial(mla_moe.init, cfg), jax.random.PRNGKey(0))
p_sh = param_shardings(mla_moe.param_logical_axes(cfg), mesh, rules)
on = lambda tree: jax.tree.map(  # noqa: E731
    lambda x: spec(x.shape, x.dtype), tree)
state = {"params": jax.tree.map(
    lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
    params, p_sh), "opt_state": on(jax.eval_shape(opt.init, params)),
    "step": spec((), jnp.int32)}


def step(state, batch):
    loss, grads = jax.value_and_grad(partial(
        mla_moe.loss_fn, config=cfg, mesh=mesh, rules=rules))(
            state["params"], batch)
    updates, new_opt = opt.update(grads, state["opt_state"], state["params"])
    return {"params": optax.apply_updates(state["params"], updates),
            "opt_state": new_opt, "step": state["step"] + 1}, loss


tokens = jax.ShapeDtypeStruct((4, 2048), jnp.int32,
                              sharding=train.batch_sharding(mesh, rules))
out["params"] = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
out["num_params"] = cfg.num_params()
try:
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        state, {"inputs": tokens, "targets": tokens}).compile()
    out["step"] = "compiled"
    out["flash_counters"] = {
        name: n for name, n in device_profiler.snapshot()["counters"].items()
        if name in ("flash.kernels", "flash.kernels_vmem_stated",
                    "flash.bwd_stat_column_bytes", "flash.bwd_stat_row_bytes")}
    out["step_argument_bytes"] = compiled.memory_analysis() \
        .argument_size_in_bytes
    # the ops that run as events of their own: every instruction outside a
    # fused computation's body, as the trace names it (its text up to the
    # operands) with the scope its metadata keeps
    ops, fused = [], False
    for line in compiled.as_text().splitlines():
        if re.match(r"^%?fused_computation|^%?\S*fused\S* \(", line):
            fused = True
        elif line.startswith("}"):
            fused = False
        elif not fused:
            m = re.match(r"^\s*(?:ROOT )?(%[\w.\-]+ = \(?\w+\[[\d,]*\]\S* "
                         r"(?:\S+ )*?[\w\-]+\()", line)
            if m and " parameter(" not in line and " constant(" not in line \
                    and " get-tuple-element(" not in line \
                    and " bitcast(" not in line and " tuple(" not in line:
                scope = re.search(r'op_name="([^"]*)"', line)
                text = line.strip()
                ops.append([text[:400] if "tpu_custom_call" not in text
                            else re.sub(r"custom-call\(.*", "custom-call(%a),"
                                        ' custom_call_target="tpu_custom_call"',
                                        text),
                            scope.group(1) if scope else ""])
    out["ops"] = ops
except Exception as e:  # noqa: BLE001 - a refusal is the finding
    out["step"] = str(e)[:600]
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def compiled():
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               PYTHONPATH=REPO_ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    config = os.path.join(REPO_ROOT, "benchmarks", "configs",
                          "xing4.0-29b-a4b-train-1chip.json")
    proc = subprocess.run(
        [sys.executable, "-c", f"CONFIG = {config!r}\n" + _SCRIPT], env=env,
        capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def _queries_of_the_cell():
    """{metric: its trace query} for every per-layer metric the cell is
    listed under that has one."""
    with open(os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    out = {}
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", [CELL]):
            with open(os.path.join(REPO_ROOT, "benchmarks", "metrics",
                                   m["name"] + ".json")) as f:
                spec = json.load(f)
            if "op" in spec.get("trace_query", {}):
                out[m["name"]] = re.compile(spec["trace_query"]["op"])
    return out


def test_the_whole_step_is_placed_on_one_v5e_chip(compiled):
    """1 dense + 8 expert layers + the MTP block at the published widths,
    8 of 64 experts, 16,384 vocabulary rows, B 4 x S 2,048 under remat
    "residuals" and CE chunks of 1,024: 1,427,179,100 parameters, to the
    parameter what `num_params` says, 7.975 GiB of arguments (weights and
    two AdamW moments, bf16), and the compiler places the step in 15.75
    GiB."""
    assert compiled["device_kind"] == "TPU v5 lite"
    assert compiled["params"] == compiled["num_params"] == 1_427_179_100
    assert compiled["step"] == "compiled", compiled["step"]
    assert compiled["step_argument_bytes"] / 2**30 == pytest.approx(
        7.975, abs=0.01)


def test_the_steps_kernels_are_the_flash_calls_and_the_grouped_matmuls(
        compiled):
    """The MLA call in parts lowers to the three flash kernels a layer body
    (the dense layer, the scanned expert layers' one body, the MTP block's,
    each again where remat reruns the forward), which the accepted MLA
    rooflines take by their outputs; a share's combine is the `tgmm` call
    that writes [32,256,3584], which `xing_moe_combine_time_share` takes,
    and not the embedding's gradient's, [64,256,3584]; every other Pallas
    call is a grouped matmul or a move of rows of a share, and none is the
    path's (plain `jnp`: a later kernel's name will hold `hc.`)."""
    queries = _queries_of_the_cell()
    assert {"mla_flash_fwd_roofline", "mla_flash_bwd_roofline",
            "hc_time_share", "xing_moe_held_time_share",
            "xing_moe_combine_time_share"} <= set(queries)
    calls = [op for op, _ in compiled["ops"] if "tpu_custom_call" in op]
    fwd = [c for c in calls if queries["mla_flash_fwd_roofline"].search(c)]
    bwd = [c for c in calls if queries["mla_flash_bwd_roofline"].search(c)]
    assert len(fwd) >= 3 and len(bwd) == 2 * 3
    assert all("[4,32,2048,128]" in c for c in fwd)
    assert not [c for c in calls if queries["hc_time_share"].search(c)]
    combine = [c for c in calls
               if queries["xing_moe_combine_time_share"].search(c)]
    assert combine and all("bf16[32,256,3584]" in c for c in combine)
    assert any("bf16[64,256,3584]" in c for c in calls)
    of_the_path = [op for op, scope in compiled["ops"]
                   if "tpu_custom_call" in op and "hc." in scope]
    taken = [op for op, _ in compiled["ops"]
             if queries["hc_kernel_time_share"].search(op)]
    assert taken == of_the_path and len(taken) >= 30
    by_kind = {}
    for op in taken:
        name, outputs = op.split(" = ")[0], op.split(" custom-call")[0]
        kind = (re.match(r"%(hc\.\w+)\.", name)[1], outputs.count("["))
        by_kind[kind] = by_kind.get(kind, 0) + 1
    # (forward, results) a body and its rerun; (backward, results) a body
    assert set(by_kind) == {("hc.pre", 4), ("hc.post", 1), ("hc.post", 2),
                            ("hc.pre", 3)}, by_kind
    assert by_kind["hc.pre", 4] == 2 * 2 * 3
    assert by_kind["hc.post", 2] == by_kind["hc.pre", 3] == 2 * 3
    assert 2 * 3 <= by_kind["hc.post", 1] <= 2 * 2 * 3
    for name, query in queries.items():
        if name != "hc_kernel_time_share":
            assert not [op for op in taken if query.search(op)], name
    assert len(calls) > len(fwd) + len(bwd) + len(combine) + len(taken)


def _output_bytes(op):
    m = re.search(r"= \(?(\w+)\[([\d,]*)\]", op)
    return math.prod(int(d) for d in m[2].split(",") if d) \
        * {"bf16": 2, "pred": 1}.get(m[1], 4)


def test_the_paths_query_is_a_floor_and_takes_no_other_layers_op(compiled):
    """`hc_time_share`'s query over every op of the compiled step, against
    the scope in the op's metadata, now that a connection's passes are
    Pallas calls (PR 62; until then it took 42% of the output bytes of the
    path's large ops, 0.37 < taken < 0.47). What it takes is the path's XLA
    REMNANTS: ops under no scope at all, the copies the compiler makes of
    the streams (the layers' saved inputs, the scan's stacking, moves
    between memories) and phi's update, and `hc.expand` / `hc.reduce`;
    never an op that MLA, the experts, the head or the embedding scope, and
    over this text none of the Pallas calls. Of the path's XLA ops it misses
    the small ones the calls' operands are laid out by (phi as rows
    [48,14336] and, a stream at a time and twice over, [4,128,3584], alpha
    and b as [48,2], their gradients back) and `hc.reduce`'s [4,2048,3584]: of the output bytes of
    the path's large XLA ops, the calls apart, 77% are taken, and of ALL
    its large ops' bytes, the calls in, 16%: `hc_kernel_time_share` reads
    the rest."""
    q = _queries_of_the_cell()["hc_time_share"]
    took = [(op, scope) for op, scope in compiled["ops"] if q.search(op)]
    of_the_path = [(op, scope) for op, scope in compiled["ops"]
                   if "hc." in scope]
    assert 50 < len(took) < 200
    assert not [op for op, _ in took if "tpu_custom_call" in op]
    for op, scope in took:   # never an op another part of the step scopes
        for other in ("mla.", "moe.", "mtp.block/moe", "ce.", "embed."):
            assert other not in scope or "hc." in scope, (op, scope)
    missed = [op for op, scope in of_the_path
              if not q.search(op) and _output_bytes(op) > 64
              and "tpu_custom_call" not in op]
    small = re.compile(
        r"= \(?\w+\[(4,2048,3584|8192,3584|(4|24|48),14336|(24|48),2|24,1|"
        r"24|48|128|4,4,3584|4,3,8,3584|(4,48|4,128|24,4),3584)\]")
    unexplained = [op for op in missed if not small.search(op)]
    assert not unexplained, unexplained[:5]
    large = [op for op, _ in of_the_path if _output_bytes(op) >= 2**20]
    xla = [op for op in large if "tpu_custom_call" not in op]

    def taken(ops):
        return sum(_output_bytes(op) for op in ops if q.search(op)) \
            / sum(_output_bytes(op) for op in ops)

    assert 0.70 < taken(xla) < 0.85, taken(xla)
    assert 0.12 < taken(large) < 0.21, taken(large)


def test_the_share_query_takes_the_routed_block_and_none_of_the_path(
        compiled):
    """`xing_moe_held_time_share`'s query takes every `conditional` (the
    capacity switches) and router-shaped ops, and no op of the path: the
    maps are [4,8192], [16,8192] and [4,4,8192], tokens minor, the router's
    [8192,64] and [8192,4]."""
    queries = _queries_of_the_cell()
    q = queries["xing_moe_held_time_share"]
    took = [(op, scope) for op, scope in compiled["ops"] if q.search(op)]
    assert sum(" conditional(" in op for op, _ in took) >= 4
    assert any("moe.route" in scope for _, scope in took)
    assert not [s for _, s in took if "hc." in s]
    both = [op for op, _ in took if queries["hc_time_share"].search(op)
            or queries["hc_kernel_time_share"].search(op)]
    assert not both


def test_the_steps_flash_calls_state_the_vmem_limits_the_parents_did(compiled):
    """The flash kernels this process lowered, as their lowerings counted
    them (`flash.kernels_vmem_stated` of `flash.kernels` is what
    `flash_vmem_stated_share` reads): the counts of PR 62, the parent of the
    PR that hands lse and delta to the backward kernels lane-dense (PR 63):
    NO call of this step states a limit (beside a share's routed block's
    backward pass every Pallas call that STATED one hung the v5e, PERF.md
    section 6, PR 62). And no statistic reaches a backward kernel as an `f32[.., 1]`
    column, 128 lanes a number."""
    counted = compiled["flash_counters"]
    assert (counted["flash.kernels"],
            counted["flash.kernels_vmem_stated"]) == (12, 0)
    assert counted["flash.bwd_stat_column_bytes"] == 0
    assert counted["flash.bwd_stat_row_bytes"] > 0
