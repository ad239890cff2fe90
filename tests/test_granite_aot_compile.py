"""`train-granite4-1chip` as the v5e's compiler sees it, with no chip
(`jax.experimental.topologies`, as tests/test_tpu_aot_compile.py): the whole
train step at the published widths and S 32,768 is PLACED on one chip's
HBM; the three state-space kernels at ONE group of 64 heads (walked in four
head blocks) and the three flash kernels at 64-wide heads compile, each
flash kernel under a VMEM limit of its own; and every trace query the cell
is listed under takes the kernels it is for and no other."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "train-granite4-1chip"

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
from ray_tpu.models import blocks, granite_hybrid
from ray_tpu.ops import ssd as ssd_op
from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.parallel.sharding import (
    LogicalAxisRules, logical_sharding, param_shardings)

topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
mesh = build_mesh(MeshConfig(dp=1, fsdp=1, tp=1), devices=topo.devices[:1])
rules = LogicalAxisRules()
one_chip = logical_sharding(mesh, (), rules)
out = {"device_kind": topo.devices[0].device_kind}
bf16, f32 = jnp.bfloat16, jnp.float32
S = 32768


def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def kernels(hlo):
    return [re.sub(r"custom-call\(.*", 'custom-call(%a), custom_call_target='
                   '"tpu_custom_call"', ln.strip())
            for ln in hlo.splitlines()
            if "tpu_custom_call" in ln and " = " in ln]


# `use_pallas` follows jax.default_backend(), cpu here
blocks.flash_attention = partial(flash_attention, use_pallas=True)
scan = ssd_op.ssd_scan
ssd_op.ssd_scan = partial(scan, use_pallas=True)

# the three state-space kernels at the cell's shape and chunk
ssd_args = (spec((1, S, 64, 64), bf16), spec((1, S, 64), f32),
            spec((1, S, 64), f32), spec((1, S, 1, 128), bf16),
            spec((1, S, 1, 128), bf16))
for chunk in (256, 128):
    try:
        hlo = jax.jit(jax.value_and_grad(
            lambda *a: scan(*a, chunk=chunk, use_pallas=True)[0].astype(
                f32).sum(), argnums=(0, 1, 2, 3, 4))).lower(
                    *ssd_args).compile().as_text()
        out[f"ssd_calls_{chunk}"] = kernels(hlo)
    except Exception as e:  # noqa: BLE001 - a refusal is the finding
        out[f"ssd_calls_{chunk}"] = str(e)[:400]

# the flash call, [1, 32768, 32, 64] over 8 KV heads, scale 1 / 64
flash_call = jax.value_and_grad(
    lambda q, k, v: flash_attention(q, k, v, scale=1 / 64, use_pallas=True)
    .astype(f32).sum(), argnums=(0, 1, 2))
flash_shapes = (spec((1, S, 32, 64), bf16), spec((1, S, 8, 64), bf16),
                spec((1, S, 8, 64), bf16))
out["flash_vmem_limits"] = re.findall(
    r"vmem_limit_bytes=(\d+)", str(jax.make_jaxpr(flash_call)(*flash_shapes)))
try:
    out["flash_calls"] = kernels(jax.jit(flash_call).lower(
        *flash_shapes).compile().as_text())
except Exception as e:  # noqa: BLE001
    out["flash_calls"] = str(e)[:400]

# the whole step as `benchmarks/train_cell.py` builds it (AdamW, the state
# donated), from the configuration file
with open(CONFIG) as f:
    config = json.load(f)
program = config["program"]
fields = {k: config[v] for k, v in program["fields_from"].items()}
fields.update(program["fields"])
cfg = granite_hybrid.GraniteHybridConfig(**fields)
opt = optax.adamw(3e-4, weight_decay=0.0)
params = jax.eval_shape(partial(granite_hybrid.init, cfg),
                        jax.random.PRNGKey(0))
p_sh = param_shardings(granite_hybrid.param_logical_axes(cfg), mesh, rules)
on = lambda tree: jax.tree.map(  # noqa: E731
    lambda x: spec(x.shape, x.dtype), tree)
state = {"params": jax.tree.map(
    lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
    params, p_sh), "opt_state": on(jax.eval_shape(opt.init, params)),
    "step": spec((), jnp.int32)}


def step(state, batch):
    loss, grads = jax.value_and_grad(partial(
        granite_hybrid.loss_fn, config=cfg, mesh=mesh, rules=rules))(
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
    out["step_calls"] = kernels(compiled.as_text())
    out["step_argument_bytes"] = compiled.memory_analysis() \
        .argument_size_in_bytes
except Exception as e:  # noqa: BLE001
    out["step"] = str(e)[:600]
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def compiled():
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               PYTHONPATH=REPO_ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    config = os.path.join(REPO_ROOT, "benchmarks", "configs",
                          "granite-4.0-h-micro-train-1chip.json")
    proc = subprocess.run(
        [sys.executable, "-c", f"CONFIG = {config!r}\n" + _SCRIPT], env=env,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def _query(name):
    with open(os.path.join(REPO_ROOT, "benchmarks", "metrics",
                           name + ".json")) as f:
        return re.compile(json.load(f)["trace_query"]["op"])


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
    """Ten layers at the published widths, the whole vocabulary, B 1 x S
    32,768 under remat "residuals" and CE chunks of 1,024: 951,991,232
    parameters, 5.32 GiB of arguments (weights and two AdamW moments,
    bf16), and the compiler places the step in 15.75 GiB."""
    assert compiled["device_kind"] == "TPU v5 lite"
    assert compiled["params"] == 951_991_232
    assert compiled["step"] == "compiled", compiled["step"]
    assert compiled["step_argument_bytes"] / 2**30 == pytest.approx(
        5.32, abs=0.01)


def test_the_steps_kernels_are_the_scans_and_the_flash_calls(compiled):
    """One trace of each layer body: three state-space kernels (a period's
    two runs of Mamba-2 layers lower a body each: six) and three flash
    kernels, and nothing else is a Pallas call."""
    calls = compiled["step_calls"]
    ssd = [c for c in calls if _query("ssd_time_share").search(c)]
    flash = [c for c in calls if _query("flash_fwd_roofline").search(c)
             or _query("flash_bwd_roofline").search(c)]
    assert len(ssd) == 6 and len(flash) == 3
    assert len(calls) == 9


@pytest.mark.parametrize("chunk", [256, 128])
def test_the_scan_at_one_group_of_64_heads_compiles_for_v5e(compiled, chunk):
    """Three Pallas calls under their own signatures, the outputs' dims the
    CALL's (y and dx [1, S, 4096], the states [1 group, chunks x 128,
    4096]) though a kernel instance holds a block of 16 heads."""
    calls = compiled[f"ssd_calls_{chunk}"]
    assert isinstance(calls, list) and len(calls) == 3, calls
    took = lambda name: [bool(_query(name).search(c)) for c in calls]  # noqa: E731
    assert sorted(took("ssd_fwd_roofline")) == [False, False, True]
    assert sorted(took("ssd_bwd_roofline")) == [False, True, True]
    assert took("ssd_time_share") == [True] * 3
    states = 32768 // chunk * 128
    joined = " ".join(calls)
    assert f"f32[1,{states},4096]" in joined
    assert "bf16[1,32768,4096]" in joined
    assert "bf16[1,32768,512]" in joined     # dB, dC: a part a head block


def test_the_flash_call_at_64_wide_heads_compiles_for_v5e(compiled):
    """Forward, dq and dk/dv at [1, 32768, 32, 64] over 8 KV heads: each
    states a VMEM limit reckoned with the 64-wide blocks at 128 lanes (the
    compiler's refusal before: "Scoped allocation with size 33.00M and
    limit 32.25M")."""
    calls = compiled["flash_calls"]
    assert isinstance(calls, list) and len(calls) == 3, calls
    limits = [int(x) for x in compiled["flash_vmem_limits"]]
    assert len(limits) == 3 and all(
        48 * 2**20 < x < 100 * 2**20 for x in limits)
    took = lambda name: [bool(_query(name).search(c)) for c in calls]  # noqa: E731
    assert sorted(took("flash_fwd_roofline")) == [False, False, True]
    assert sorted(took("flash_bwd_roofline")) == [False, True, True]


def test_every_query_of_the_cell_over_every_kernel_of_the_step(compiled):
    """Each trace query the cell is listed under, run over every Pallas
    event name of the compiled step: a kernel is taken by the queries that
    are for it and by no other."""
    queries = _queries_of_the_cell()
    assert {"ssd_fwd_roofline", "ssd_bwd_roofline", "ssd_time_share",
            "flash_fwd_roofline", "flash_bwd_roofline"} <= set(queries)
    ssd = {"ssd_fwd_roofline", "ssd_bwd_roofline", "ssd_time_share"}
    flash = {"flash_fwd_roofline", "flash_bwd_roofline"}
    for call in compiled["step_calls"]:
        took = {name for name, q in queries.items() if q.search(call)}
        kernel_queries = took & (ssd | flash)
        assert kernel_queries, call
        # a state-space kernel: its time share and ONE of the rooflines
        if took & ssd:
            assert kernel_queries == {"ssd_time_share"} | (
                kernel_queries & {"ssd_fwd_roofline", "ssd_bwd_roofline"})
            assert len(kernel_queries) == 2, call
        else:
            assert len(kernel_queries) == 1, call


def test_the_steps_flash_calls_state_the_vmem_limits_the_parents_did(compiled):
    """The flash kernels this process lowered, as their lowerings counted
    them (`flash.kernels_vmem_stated` of `flash.kernels` is what
    `flash_vmem_stated_share` reads): the counts of PR 62, the parent of the
    PR that hands lse and delta to the backward kernels lane-dense (PR 63):
    every call at S 32,768 states one, as it did. And no statistic reaches a backward kernel as an `f32[.., 1]`
    column, 128 lanes a number."""
    counted = compiled["flash_counters"]
    assert (counted["flash.kernels"],
            counted["flash.kernels_vmem_stated"]) == (7, 7)
    assert counted["flash.bwd_stat_column_bytes"] == 0
    assert counted["flash.bwd_stat_row_bytes"] > 0
