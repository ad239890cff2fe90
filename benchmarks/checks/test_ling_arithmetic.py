"""Checks of the pattern cell's files (`train-ling-1chip`: KDA and MLA
layers to a period), its arithmetic, readers and queries; a minute on the
CPU, no chip:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/checks -q
"""

import importlib
import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import kda_readers, opcount_ling, peaks  # noqa: E402

CELL = "train-ling-1chip"
CONFIG = "ling-3.0-flash-vl-train-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("kda_fwd_roofline", "kda_time_share",
               "ling_moe_held_time_share", "kda_bwd_roofline")


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _json("benchmarks", "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def model(config):
    program = config["program"]
    fields = {f: config[k] for f, k in program["fields_from"].items()}
    return dict(fields, **program["fields"])


def test_configuration_keeps_every_published_number(config):
    """Every key of the catalog's `config` under the same name and value,
    but the three that are the chip's share or the depth; no width among
    them; the floors of a `model_config` PR."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Ling-3.0-flash-VL"]
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    published = config["deployment"]["published"]
    assert published == {k: row["config"][k] for k in config["reduced"]}
    chips = config["deployment"]["chips_sharing_a_layer"]
    assert config["num_experts"] * chips == published["num_experts"]
    assert config["router_outputs"] == published["num_experts"]
    # floors: a whole period and >= 4 layers after the dense one, >= 8
    # experts, >= 1/8 of the vocabulary
    held = config["layers_held"]
    assert len(held) == config["num_hidden_layers"]
    period = config["layer_group_size"]
    after = [i for i in held if i >= config["first_k_dense_replace"]]
    assert len(after) >= max(4, period) and after[0] % period == 0
    assert after == list(range(after[0], after[0] + period))
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 == published["vocab_size"]
    # no held layer clamps its SwiGLU (the form is not published)
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert all(config[key][i] == 0 for i in after)


def test_parameters_against_the_issues_table_and_the_program(model):
    """ISSUE 39's table, to the parameter: 1,167,575,360 (its 1.1675 B is
    the table's parts rounded to 10^4 before they are added up)."""
    kda = 6 * 10_485_760 + 2560 * 32 + 3 * 4096 * 4 + 32 + 4096 + 128
    mla = (15_728_640 + 2560 * 576 + 512 + 4_194_304 + 10_485_760
           + 2560 * 32 + 2 * 192)
    routed = 2560 * 512 + 512 + 5_898_240 + 16 * 5_898_240
    assert (kda, mla, routed) == (63_049_888, 31_966_080, 101_581_312)
    assert opcount_ling.kda_params(model) == kda
    assert opcount_ling.mla_params(model) == mla
    assert opcount_ling.routed_params(model) == routed
    want = (kda + 2 * 2560 + 3 * 2560 * 6144
            + 5 * (kda + 2 * 2560 + routed) + mla + 2 * 2560 + routed
            + 2 * 19_648 * 2560 + 2560)
    assert opcount_ling.num_params(model) == want == 1_167_575_360
    from ray_tpu.models import hybrid_moe

    cfg = hybrid_moe.HybridMoeConfig(**model)
    assert cfg.num_params() == want
    assert cfg.plan()[3] == [("dense", 1), ("periods", 1)]


def test_operation_counts_against_hand_sums(model):
    chunk = 2 * 64 * 64 * (3 * 128 + 2 * 128) + 6 * 64 * 128 * 128 \
        + 2 * 64 ** 3 / 3
    assert opcount_ling.kda_chunk_ops(128, 128) == chunk
    kda = 2 * (6 * 10_485_760 + 2560 * 32 + 3 * 4 * 4096) + 32 * chunk / 64
    mla = 2 * (15_728_640 + 2560 * 576 + 4_194_304 + 10_485_760
               + 2560 * 32) + 32 * 2048 * (192 + 128)
    # router, the shared expert, 8 x 16 / 512 = 0.25 held pairs a token
    routed = 2 * (2560 * 512 + 1.25 * 5_898_240)
    forward = (kda + 2 * 3 * 2560 * 6144 + 5 * (kda + routed) + mla + routed
               + 2 * 2560 * 19_648)
    assert opcount_ling.forward_flops_per_token(model, 2048) \
        == pytest.approx(forward, rel=1e-12)
    frozen = 6 * 2560 * 512
    assert opcount_ling.frozen_router_params(model) == frozen
    got = opcount_ling.train_flops_per_token(model, 2048)
    assert got == pytest.approx(3 * forward - 4 * frozen, rel=1e-12)
    assert round(forward / 1e9, 3) == 1.176 and round(got / 1e9, 3) == 3.496
    # what the configuration's `why_reduced` says: the head's share
    assert round(100 * 2 * 2560 * 19_648 / forward, 1) == 8.6


def test_kernel_bounds_at_the_cells_shape():
    peak = peaks.peaks("TPU v5 lite")
    ops, nbytes = opcount_ling.kda_fwd(4, 32, 2048, 128, 128)
    assert ops == 4 * 32 * 32 * opcount_ling.kda_chunk_ops(128, 128)
    assert nbytes == 4 * 32 * (2048 * (2 * 4 * 128 + 4 * 129)
                               + 4 * 128 * 128)
    fwd = opcount_ling.bound_seconds(ops, nbytes, peak)
    assert fwd == nbytes / 819e9 > ops / 197e12          # memory-bound
    assert fwd == pytest.approx(0.503e-3, rel=2e-3)
    assert ops / 197e12 == pytest.approx(0.243e-3, rel=2e-3)
    ops_b, nbytes_b = opcount_ling.kda_bwd(4, 32, 2048, 128, 128)
    assert ops_b == 2 * ops and nbytes_b > nbytes
    bwd = opcount_ling.bound_seconds(ops_b, nbytes_b, peak)
    assert bwd == nbytes_b / 819e9 == pytest.approx(0.904e-3, rel=2e-3)
    from benchmarks import opcount_joyai
    assert opcount_ling.flash_fwd is opcount_joyai.flash_fwd
    assert opcount_ling.flash_bwd is opcount_joyai.flash_bwd


def _ctx(model, name, opcount="opcount_ling"):
    return {"name": name, "model": model, "opcount": opcount,
            "device_kind": "TPU v5 lite",
            "traffic": _json("benchmarks", "traffic", "pretrain-2k.json")}


def test_kda_roofline_reader_on_synthetic_queries(model):
    name = "kda_fwd_roofline"
    spec = _json("benchmarks", "metrics", name + ".json")
    ctx = _ctx(model, name)
    ops, nbytes = opcount_ling.kda_fwd(4, 32, 2048, 128, 128)
    bound = nbytes / 819e9
    q = {"total_s": 24 * 4 * bound, "count": 24, "dims": [128, 2048, 128]}
    got = kda_readers.kernel_roofline(
        spec, {"trace": {"queries": {name: q}}}, ctx)
    assert got == pytest.approx(25.0, rel=1e-6)
    # the backward pass is two events a call
    name_b = "kda_bwd_roofline"
    spec_b = _json("benchmarks", "metrics", name_b + ".json")
    bound_b = opcount_ling.kda_bwd(4, 32, 2048, 128, 128)[1] / 819e9
    q_b = {"total_s": 12 * 5 * bound_b, "count": 24, "dims": [128, 2048, 128]}
    assert kda_readers.kernel_roofline(
        spec_b, {"trace": {"queries": {name_b: q_b}}},
        _ctx(model, name_b)) == pytest.approx(20.0, rel=1e-6)
    flash = {"total_s": 1.0, "count": 3, "dims": [4, 32, 2048, 128]}
    odd = {"total_s": 1.0, "count": 3, "dims": [128, 2048, 64]}
    for readings in ({"trace": {"queries": {name: flash}}},
                     {"trace": {"queries": {name: odd}}},
                     {"trace": {"queries": {name: None}}},
                     {"trace": {"queries": {}}}, {"trace": None}, {}):
        assert kda_readers.kernel_roofline(spec, readings, ctx) is None
    # the parent's cells have no such field: nothing is read, nothing raises
    llama = {"d_model": 4096, "n_heads": 32, "n_kv_heads": 8, "d_head": 128}
    assert kda_readers.kernel_roofline(
        spec, {"trace": {"queries": {name: q}}},
        _ctx(llama, name, "opcount")) is None


# one event of every Pallas kernel the step has, as the xplane names them
_TAIL = (' custom-call(%a, %b), custom_call_target="tpu_custom_call", '
         'backend_config={}')
KERNEL_EVENTS = {
    "kda_fwd": ("%kda.scan.7 = (bf16[128,2048,128]{2,1,0:T(8,128)(2,1)}, "
                "f32[128,128,128]{2,1,0:T(8,128)})" + _TAIL),
    "kda_states": ("%kda.scan.8 = (bf16[128,2048,128]{2,1,0:T(8,128)(2,1)}, "
                   "f32[128,2048,64]{2,1,0:T(8,128)}, "
                   "f32[128,4096,128]{2,1,0:T(8,128)})" + _TAIL),
    "kda_grads": ("%kda.scan.9 = (bf16[128,2048,128]{2,1,0:T(8,128)(2,1)}, "
                  "bf16[128,2048,128]{2,1,0:T(8,128)(2,1)}, "
                  "bf16[128,2048,128]{2,1,0:T(8,128)(2,1)}, "
                  "f32[128,2048,128]{2,1,0:T(8,128)}, "
                  "f32[128,32,64]{2,1,0:T(8,128)})" + _TAIL),
    "flash_fwd": ("%mla.attend.1 = (bf16[4,32,2048,128]{3,2,1,0}, "
                  "f32[4,32,2048,1]{3,2,1,0})" + _TAIL),
    "flash_dq": "%mla.attend.2 = bf16[4,32,2048,128]{3,2,1,0:T(8,128)(2,1)}"
                + _TAIL,
    "flash_dkv": ("%mla.attend.3 = (bf16[4,32,2048,128]{3,2,1,0}, "
                  "bf16[4,32,2048,128]{3,2,1,0})" + _TAIL),
    "gmm": "%gmm.3 = bf16[4096,768]{1,0:T(8,128)(2,1)}" + _TAIL,
    "tgmm": "%tgmm.1 = bf16[16,2560,768]{2,1,0:T(8,128)(2,1)}" + _TAIL,
    "row_sums": "%tgmm.9 = bf16[32,256,2560]{2,1,0:T(8,128)(2,1)}" + _TAIL,
}
# the kernels each of the cell's KERNEL queries is for; every other
# kernel's event has to slip through it
QUERY_TAKES = {
    "kda_fwd_roofline": {"kda_fwd"},
    "kda_bwd_roofline": {"kda_states", "kda_grads"},
    "mla_flash_fwd_roofline": {"flash_fwd"},
    "mla_flash_bwd_roofline": {"flash_dq", "flash_dkv"},
}


@pytest.mark.parametrize("metric", sorted(QUERY_TAKES))
def test_a_kernel_query_takes_its_kernels_and_no_other(metric):
    rx = re.compile(_json("benchmarks", "metrics", metric + ".json")[
        "trace_query"]["op"])
    took = {k for k, event in KERNEL_EVENTS.items() if rx.search(event)}
    assert took == QUERY_TAKES[metric]


def test_the_share_queries_take_no_flash_event():
    """`kda_time_share` takes the three KDA kernels,
    `ling_moe_held_time_share` no Pallas call by name (the grouped matmuls
    run inside its conditionals)."""
    query = lambda n: re.compile(_json(  # noqa: E731
        "benchmarks", "metrics", n + ".json")["trace_query"]["op"])
    took = {k for k, e in KERNEL_EVENTS.items()
            if query("kda_time_share").search(e)}
    assert took == {"kda_fwd", "kda_states", "kda_grads"}
    assert not any(query("ling_moe_held_time_share").search(e)
                   for e in KERNEL_EVENTS.values())


def test_benchmark_json_lists_the_cell_where_its_readers_read():
    bench = _json("BENCHMARK.json")
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain-2k", 1)
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    listed = {m["name"]: m.get("workloads", [])
              for m in bench["per_layer"] + bench["end_to_end"]}
    for name in ("train_tokens_per_s_per_chip", "train_mfu",
                 "train_step_p50_ms", "device_idle_share.train",
                 "peak_hbm_bytes.train", "mla_flash_fwd_roofline",
                 "mla_flash_bwd_roofline", "flash_unmasked_step_share",
                 "moe_gmm_partial_tile_share") + NEW_METRICS:
        assert CELL in listed[name], name
    # their readers want n_kv_heads and one width, other cells' shapes, or
    # (the combine's) D 2,048
    for name in ("flash_fwd_roofline", "flash_bwd_roofline",
                 "moe_gmm_roofline", "moe_dispatch_time_share",
                 "moe_held_time_share", "sdar_moe_held_time_share",
                 "moe_combine_time_share", "bd_attention_time_share"):
        assert CELL not in listed[name], name
    for name in NEW_METRICS:
        assert listed[name] == [CELL]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_file_names_a_reader_that_exists(name):
    spec = _json("benchmarks", "metrics", name + ".json")
    module, fn = spec["reader"].rsplit(".", 1)
    assert callable(getattr(
        importlib.import_module("benchmarks." + module), fn))
    if "opcount" in spec:
        assert callable(getattr(opcount_ling, spec["opcount"]))
    if "trace_query" in spec:
        re.compile(spec["trace_query"]["op"])


def test_rehearsal_runs_the_cells_files():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2200390001", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 3, done.stderr[-2000:]
    (line,) = [ln for ln in done.stdout.splitlines()
               if ln.startswith('{"rehearsal"')]
    r = json.loads(line)["rehearsal"]
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    assert r["checks"]["loss_rel_err"] <= 3e-4
    # off the chip the trace and the Pallas kernels' counters read nothing
    readable = {k for k, v in r["metric_was_readable"].items() if v is True}
    assert "train_step_p50_ms" in readable
    assert not readable & {"kda_fwd_roofline", "kda_bwd_roofline",
                           "kda_time_share",
                           "ling_moe_held_time_share",
                           "mla_flash_fwd_roofline"}
