"""`train-solar2-1chip` as the v5e's compiler sees it, with no chip
(`tools/step_lowering_hash.py --cell .. --memory`, which builds the cell as
`benchmarks/train_cell.py` does on a described v5e, every kernel on its
Pallas branch): the whole train step at the published widths, from the
configuration file, is PLACED on one chip's HBM at the depth and share the
file states, and a second period is refused; its Pallas calls are the flash
kernels of the one GQA layer, `ops/kda.py`'s three under the ANY-DECAY plan
and the share's grouped matmuls and row moves; and every trace query the
cell is listed under, run over the compiled step's op names (what the
device trace names its events by), takes the ops it is for and no other
layer's."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "train-solar2-1chip"
CHIP_BYTES = 15.75 * 2 ** 30


def _tool(*args, timeout=1500):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
               PYTHONPATH=REPO_ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    return subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools",
                                      "step_lowering_hash.py"),
         "--cell", CELL, "--memory", *args],
        env=env, capture_output=True, text=True, timeout=timeout)


@pytest.fixture(scope="module")
def compiled(tmp_path_factory):
    out = tmp_path_factory.mktemp("solar2")
    proc = _tool("--out", str(out))
    assert proc.returncode == 0, proc.stderr[-3000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith("MEMORY ")]
    with open(os.path.join(out, CELL + ".ops.json")) as f:
        return dict(json.load(f), memory=json.loads(line[len("MEMORY "):]))


def _queries_of_the_cell():
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
    """Published layers 0-3 at the published widths, 10 of 320 experts,
    24,576 vocabulary rows, B 1 x S 8,192 under remat "residuals" and CE
    chunks of 1,024: 1,420,916,544 parameters, 7.94 GiB of arguments
    (weights and two AdamW moments, bf16: over half the chip), and the
    compiler places the step in 15.75 GiB."""
    memory = compiled["memory"]
    assert memory["argument"] / 2 ** 30 == pytest.approx(7.94, abs=0.01)
    assert memory["argument"] > 0.5 * CHIP_BYTES
    assert memory["argument"] == pytest.approx(6 * 1_420_916_544, rel=1e-3)


def test_a_second_period_is_refused():
    """Published layers 0-7 (2,641 M parameters: 14.8 GiB of arguments
    alone): `Used 30.28G of 15.75G hbm`."""
    proc = _tool("--over", json.dumps({"layers": list(range(8))}))
    assert proc.returncode != 0
    m = re.search(r"Ran out of memory in memory space hbm. Used ([\d.]+)G "
                  r"of 15.75G hbm", proc.stderr)
    assert m and float(m[1]) > 25, proc.stderr[-2000:]


def test_the_steps_kernels_and_what_the_queries_take(compiled):
    """The KDA call lowers to three kernels a body (the period's three KDA
    layers are ONE scanned body: forward; the backward pass's two walks),
    all under the any-decay plan, which `kda_any_fwd_roofline`,
    `kda_any_bwd_roofline` and `kda_any_time_share` take by their outputs
    (Ling's queries in files of this cell's own); the GQA layer's flash call to three (forward, dq, dk/dv)
    that `solar2_attention_time_share` takes and the KDA queries do not,
    the forward alone `flash_fwd_roofline`'s; the routed block's ops are
    `solar2_moe_held_time_share`'s and none of them a kernel of the
    mixers."""
    queries = _queries_of_the_cell()
    assert {"kda_any_fwd_roofline", "kda_any_bwd_roofline",
            "kda_any_time_share", "flash_fwd_roofline",
            "solar2_attention_time_share", "solar2_moe_held_time_share"} <= set(queries)
    counters = compiled["counters"]
    assert counters["kda.kernels"] == counters["kda.kernels_any_decay"] >= 3
    assert counters["pattern.periods"] == 1
    assert "pattern.layers_unrolled" not in counters
    calls = [op for op, _ in compiled["ops"] if "tpu_custom_call" in op]
    took = lambda name: [c for c in calls  # noqa: E731
                         if queries[name].search(c)]
    kda_fwd = took("kda_any_fwd_roofline")
    kda_bwd = took("kda_any_bwd_roofline")
    assert len(kda_fwd) == 1 and len(kda_bwd) == 2
    assert all("[64,8192,128]" in c for c in kda_fwd + kda_bwd)
    assert sorted(took("kda_any_time_share")) == sorted(kda_fwd + kda_bwd)
    attention = took("solar2_attention_time_share")
    assert len(attention) >= 3 and not set(attention) & set(kda_fwd + kda_bwd)
    assert all(re.search(r"bf16\[1,(64|8),8192,128\]", c) for c in attention)
    flash_fwd = took("flash_fwd_roofline")
    assert flash_fwd and set(flash_fwd) < set(attention)
    held = [op for op, _ in compiled["ops"]
            if queries["solar2_moe_held_time_share"].search(op)]
    assert held and not set(held) & set(attention + kda_fwd + kda_bwd)
    # the low-rank gates and the channel gate are named in the step
    scopes = {scope for _, scope in compiled["ops"]}
    assert any("kda.gate_lora" in s for s in scopes)
    assert any("attn.gate" in s for s in scopes)


def test_the_steps_operand_calls_state_no_limit_and_are_nobody_elses(compiled):
    """q, k, v and g of the period's KDA body come from `ops/kda_prep.py`'s
    calls in the compiled STEP (every row counted as fused): `prep`'s and
    `gate`'s forward, rerun under remat "residuals" (where the compiler does
    not share it) and backward, named `%kda.prep...` by
    their scope, which is how `kda_prep_time_share` takes them; no other
    metric's query that names `tpu_custom_call`, of any cell, takes one (a
    roofline read over another kernel's events would pass 100%); and the
    module states no VMEM limit (beside this step's routed block a call that
    did hung the v5e: PERF.md section 6, PR 62)."""
    import glob
    import inspect

    from ray_tpu.ops import kda_prep

    assert "vmem_limit_bytes" not in inspect.getsource(kda_prep)
    counters = compiled["counters"]
    assert counters["kda.prep_rows"] == counters["kda.prep_rows_fused"] \
        == 3 * 8192 * 64 * counters["kda.layers"]
    calls = [op for op, _ in compiled["ops"]
             if "tpu_custom_call" in op and op.startswith("%kda.prep")]
    outputs = {tuple(re.findall(r"\w+\[[\d,]*\]", c.split(" custom-call")[0]))
               for c in calls}
    assert outputs == {
        ("bf16[1,64,8192,128]",) * 3,
        ("bf16[1,8192,8192]",) * 3 + ("f32[1,12,8192]",),
        ("f32[1,64,8192,128]",),
        ("bf16[1,8192,8192]", "f32[1,64,2,128]")}, calls
    assert 4 <= len(calls) <= 6
    for path in sorted(glob.glob(os.path.join(
            REPO_ROOT, "benchmarks", "metrics", "*.json"))):
        with open(path) as f:
            query = json.load(f).get("trace_query", {}).get("op", "")
        if "tpu_custom_call" not in query:
            continue
        name = os.path.basename(path)[:-len(".json")]
        took = [c for c in calls if re.search(query, c)]
        assert took == (calls if name == "kda_prep_time_share" else []), name
    assert "kda_prep_time_share" in _queries_of_the_cell()
