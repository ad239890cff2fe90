"""`train-evabyte-1chip` as the v5e's compiler sees it, with no chip
(`jax.experimental.topologies`, as tests/test_granite_aot_compile.py): the
whole train step at the published widths and S 32,768 is PLACED on one
chip's HBM; its only Pallas calls are the three flash kernels of the EVA
call, q [1, 32768, 32, 128] over [2,048 summaries ; 32,768 bytes], each under
a VMEM limit of its own; and every trace query the cell is listed under takes
the ops it is for and no other."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "train-evabyte-1chip"

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
from ray_tpu.models import evabyte
from ray_tpu.ops import eva
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.parallel.sharding import (
    LogicalAxisRules, logical_sharding, param_shardings)

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1), devices=topo.devices[:1])
rules = LogicalAxisRules()
one_chip = logical_sharding(mesh, (), rules)
out = {"device_kind": topo.devices[0].device_kind}
bf16 = jnp.bfloat16
S = 32768


def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


# `use_pallas` follows jax.default_backend(), cpu here
eva.flash_attention = partial(flash_attention, use_pallas=True)

# the EVA call alone, as traced: the limits its three kernels state
call = jax.value_and_grad(
    lambda q, k, v, phi, mu: eva.eva_attention(q, k, v, phi, mu, 2048, 16)
    .astype(jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))
shapes = (spec((1, S, 32, 128), bf16),) * 3 + (spec((32, 128), bf16),) * 2
out["flash_vmem_limits"] = re.findall(
    r"vmem_limit_bytes=(\d+)", str(jax.make_jaxpr(call)(*shapes)))

# the whole step as `benchmarks/train_cell.py` builds it (AdamW, the state
# donated), from the configuration file
with open(CONFIG) as f:
    config = json.load(f)
program = config["program"]
fields = {k: config[v] for k, v in program["fields_from"].items()}
fields.update(program["fields"])
cfg = evabyte.EvaByteConfig(**fields)
opt = optax.adamw(3e-4, weight_decay=0.0)
params = jax.eval_shape(partial(evabyte.init, cfg), jax.random.PRNGKey(0))
p_sh = param_shardings(evabyte.param_logical_axes(cfg), mesh, rules)
on = lambda tree: jax.tree.map(  # noqa: E731
    lambda x: spec(x.shape, x.dtype), tree)
state = {"params": jax.tree.map(
    lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
    params, p_sh), "opt_state": on(jax.eval_shape(opt.init, params)),
    "step": spec((), jnp.int32)}


def step(state, batch):
    loss, grads = jax.value_and_grad(partial(
        evabyte.loss_fn, config=cfg, mesh=mesh, rules=rules))(
            state["params"], batch)
    updates, new_opt = opt.update(grads, state["opt_state"], state["params"])
    return {"params": optax.apply_updates(state["params"], updates),
            "opt_state": new_opt, "step": state["step"] + 1}, loss


tokens = jax.ShapeDtypeStruct((1, S), jnp.int32,
                              sharding=train.batch_sharding(mesh, rules))
out["params"] = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
try:
    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        state, {"inputs": tokens, "targets": tokens}).compile()
    out["step"] = "compiled"
    out["flash_counters"] = {
        name: n for name, n in device_profiler.snapshot()["counters"].items()
        if name in ("flash.kernels", "flash.kernels_vmem_stated",
                    "flash.bwd_stat_column_bytes", "flash.bwd_stat_row_bytes")}
    memory = compiled.memory_analysis()
    out["step_argument_bytes"] = memory.argument_size_in_bytes
    out["step_temp_bytes"] = memory.temp_size_in_bytes
    # every instruction as the trace names its event, with whether the
    # pooling's scope is in its metadata
    out["step_ops"] = [
        [re.sub(r"^\s*(ROOT )?", "", ln)[:400], "eva.summarise" in ln]
        for ln in compiled.as_text().splitlines()
        if re.match(r"\s*(ROOT )?%[\w.\-]+ = ", ln)
        and ("tpu_custom_call" in ln or " fusion(" in ln)]
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
                          "evabyte-train-1chip.json")
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
    """Four layers at the published widths, all 32 heads, the 320-row
    vocabulary and the 8 heads, B 1 x S 32,768 under remat "residuals", the
    MLP in 4 blocks of 8,192 rows and CE chunks of 1,024: 821,366,784
    parameters, 4.59 GiB of arguments (weights and two AdamW moments, bf16),
    and the compiler places the step in 15.75 GiB (it reports 14.84 GiB of
    temporaries, the donated arguments' room among them; five layers are
    refused: "Used 16.73G of 15.75G hbm")."""
    assert compiled["device_kind"] == "TPU v5 lite"
    assert compiled["params"] == 821_366_784
    assert compiled["step"] == "compiled", compiled["step"]
    assert compiled["step_argument_bytes"] / 2**30 == pytest.approx(
        4.59, abs=0.01)
    assert compiled["step_temp_bytes"] / 2**30 < 15.5


def test_the_steps_kernels_are_the_eva_calls_three(compiled):
    """One trace of the layer body: the forward (o and lse), dq, and dk/dv
    over the 34,816 keys, named by the rule's scope; nothing else is a
    Pallas call (the pooling is plain `jnp`)."""
    calls = [op for op, _ in compiled["step_ops"] if "tpu_custom_call" in op]
    assert len(calls) == 3 and all(
        re.match(r"%eva\.attend[\w.\-]* = ", c) for c in calls), calls
    joined = " ".join(calls)
    assert "(bf16[1,32,32768,128]" in joined and "f32[1,32,32768,1]" in joined
    assert "(bf16[1,32,34816,128]" in joined


def test_each_kernel_states_a_vmem_limit_of_its_own(compiled):
    """One head's K and V whole, twice: 34 MiB of blocks in the forward and
    dq (17 MiB of [34816, 128] K and V, the pipeline's two buffers), 32 MiB
    of q and dO in dk/dv; each limit is its blocks plus 16 MiB, far under
    the chip's 128."""
    limits = sorted(int(x) for x in compiled["flash_vmem_limits"])
    assert len(limits) == 3
    assert all(48 * 2**20 < x < 64 * 2**20 for x in limits), limits


def test_every_query_of_the_cell_over_every_op_of_the_step(compiled):
    """Each trace query the cell is listed under, run over every kernel's
    and every fusion's event name of the compiled step: a kernel is taken by
    the EVA time share and ONE of the two rooflines and by no other query;
    the pooling's time share takes a dozen fusions, nearly all of which
    carry the scope `eva.summarise` in their metadata (the trace's event
    names do not), and no kernel."""
    queries = _queries_of_the_cell()
    kernels = {"eva_flash_fwd_roofline", "eva_flash_bwd_roofline",
               "eva_attention_time_share"}
    assert kernels | {"eva_summarise_time_share"} == set(queries)
    took_by_kernel = []
    pooled = []
    for op, in_scope in compiled["step_ops"]:
        took = {name for name, q in queries.items() if q.search(op)}
        if "tpu_custom_call" in op:
            assert "eva_attention_time_share" in took and len(took) == 2, op
            took_by_kernel.append(sorted(took - {"eva_attention_time_share"}))
        else:
            assert took <= {"eva_summarise_time_share"}, op
            if took:
                pooled.append(in_scope)
    assert sorted(took_by_kernel) == [
        ["eva_flash_bwd_roofline"], ["eva_flash_bwd_roofline"],
        ["eva_flash_fwd_roofline"]]
    assert len(pooled) >= 10 and sum(pooled) >= 0.9 * len(pooled), pooled


def test_the_steps_flash_calls_state_the_vmem_limits_the_parents_did(compiled):
    """The flash kernels this process lowered, as their lowerings counted
    them (`flash.kernels_vmem_stated` of `flash.kernels` is what
    `flash_vmem_stated_share` reads): the counts of PR 62, the parent of the
    PR that hands lse and delta to the backward kernels lane-dense (PR 63):
    every call over 34,816 keys states one, as it did. And no statistic reaches a backward kernel as an `f32[.., 1]`
    column, 128 lanes a number."""
    counted = compiled["flash_counters"]
    assert (counted["flash.kernels"],
            counted["flash.kernels_vmem_stated"]) == (7, 7)
    assert counted["flash.bwd_stat_column_bytes"] == 0
    assert counted["flash.bwd_stat_row_bytes"] > 0
