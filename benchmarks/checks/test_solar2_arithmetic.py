"""Checks of the Solar cell's files (`train-solar2-1chip`: a gated NoPE GQA
layer and three KDA layers a period), its arithmetic and its metrics' files;
seconds on the CPU, no chip:

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

from benchmarks import kda_readers, opcount_solar2, peaks  # noqa: E402

CELL = "train-solar2-1chip"
CONFIG = "solar-open2-250b-train-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("kda_any_fwd_roofline", "kda_any_bwd_roofline",
               "kda_any_time_share", "solar2_attention_time_share",
               "solar2_moe_held_time_share")
APPENDED = ("flash_fwd_roofline", "flash_loop_body_step_share",
            "moe_gmm_partial_tile_share", "train_mfu")


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
                  if r["name"] == "Solar-Open2-250B"]
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    published = config["deployment"]["published"]
    assert published == {k: row["config"][k] for k in config["reduced"]}
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    assert config["layers_held"] == [0, 1, 2, 3]  # one whole period
    assert config["deployment"]["chips_sharing_a_layer"] \
        == published["n_routed_experts"] // config["n_routed_experts"] == 32
    for key in ("kda_gate", "kda_beta", "kda_output", "gqa_gate_width",
                "experts", "weights", "router_on_a_share", "layer_kinds"):
        assert len(config["assumed"][key]) > 40, key


def test_parameters_against_the_issues_table(model):
    """ISSUE 64's count by kind (its figures hold; the KDA mixer's
    137.72 M is 137,732,288 with the conv, A_log, dt_bias and the head
    norm in)."""
    assert opcount_solar2.gqa_params(model) == 109_051_904
    assert opcount_solar2.kda_params(model) == 137_732_288
    assert opcount_solar2.kda_matmul_params(model) == 4 * 33_554_432 \
        + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
    assert opcount_solar2.routed_params(model) \
        == 1_311_040 + 15_728_640 * 11
    assert opcount_solar2.num_params(model) == 1_420_916_544
    assert opcount_solar2.num_params(dict(model, n_experts_held=8)) \
        == 1_295_087_424
    # 6 B a parameter as arguments: over half of 15.75 GiB
    assert 6 * opcount_solar2.num_params(model) > 0.5 * 15.75 * 2 ** 30
    assert opcount_solar2.frozen_router_params(model) == 4 * 4096 * 320


def test_operation_counts_against_hand_sums(model):
    """~4.6 GFLOP a token trained; the three KDA layers are over half of the
    forward's ops, the head 13%, the held experts 2%."""
    seq = 8192
    kda = 2 * (opcount_solar2.kda_matmul_params(model) + 3 * 4 * 8192) \
        + 64 * opcount_solar2.kda_chunk_ops(128, 128) / 64
    gqa = 2 * 109_051_904 + 2 * 64 * 128 * seq
    routed = 2 * (4096 * 320 + (1 + 8 * 10 / 320) * 15_728_640)
    head = 2 * 4096 * 24576
    forward = 3 * kda + gqa + 4 * routed + head
    assert opcount_solar2.forward_flops_per_token(model, seq) \
        == pytest.approx(forward, rel=1e-12)
    assert opcount_solar2.train_flops_per_token(model, seq) \
        == pytest.approx(3 * forward - 4 * 4 * 4096 * 320, rel=1e-12)
    assert 4.4e9 < opcount_solar2.train_flops_per_token(model, seq) < 4.9e9
    assert 0.5 < 3 * kda / forward < 0.65
    assert 0.12 < head / forward < 0.15
    assert 8 * 10 / 320 * 2 * 15_728_640 * 4 / forward < 0.03


def test_kernel_bounds_at_the_cells_shape():
    """`kda_fwd` / `kda_bwd` at [1, 64, 8192, 128]: the definition's chunked
    matmuls against the operands' bytes, each once; both memory-bound on a
    v5e, as at Ling's shape (twice its tokens x heads: 0.487 ms of matmuls
    at the peak, 0.991 ms of bytes)."""
    peak = peaks.peaks("TPU v5 lite")
    ops, nbytes = opcount_solar2.kda_fwd(1, 64, 8192, 128, 128)
    assert ops == 64 * 128 * opcount_solar2.kda_chunk_ops(128, 128)
    assert ops / peak["bf16_flops_per_s"] == pytest.approx(0.487e-3, rel=0.02)
    assert nbytes / peak["hbm_bytes_per_s"] == pytest.approx(0.991e-3,
                                                             rel=0.02)
    b_ops, b_bytes = opcount_solar2.kda_bwd(1, 64, 8192, 128, 128)
    assert b_ops == 2 * ops and b_bytes > nbytes
    f_ops, f_bytes = opcount_solar2.flash_fwd(1, 64, 8192, 128, 8 / 64)
    assert f_ops == 2 * 2 * 64 * 8192 * 8192 * 128 / 2
    assert opcount_solar2.bound_seconds(f_ops, f_bytes, peak) \
        == f_ops / peak["bf16_flops_per_s"]      # compute-bound


def test_kda_roofline_reader_reads_this_cells_model(model):
    """The accepted KDA reader splits a kernel's flat rows by the model's
    `n_heads` and takes the widths from `kda_head_dim`: the Solar config
    carries both names, and this cell's copies of Ling's three metrics keep
    Ling's queries letter for letter."""
    for name in NEW_METRICS[:3]:
        assert _json("benchmarks", "metrics", name + ".json")["trace_query"] \
            == _json("benchmarks", "metrics",
                     name.replace("_any", "") + ".json")["trace_query"]
    spec = _json("benchmarks", "metrics", "kda_any_fwd_roofline.json")
    readings = {"trace": {"queries": {"kda_any_fwd_roofline": {
        "count": 12, "total_s": 12 * 8.9e-3, "dims": [64, 8192, 128]}}}}
    ctx = {"name": "kda_any_fwd_roofline", "model": model,
           "opcount": "opcount_solar2", "device_kind": "TPU v5 lite"}
    assert kda_readers.kernel_roofline(spec, readings, ctx) \
        == pytest.approx(100 * 0.991e-3 / 8.9e-3, rel=0.03)


def test_benchmark_json_lists_the_cell_where_its_readers_read():
    bench = _json("BENCHMARK.json")
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == dict(cell, config=CONFIG, traffic="pretrain-8k-b1",
                        chips=1)
    assert len(bench["workloads"]) == 13
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS + APPENDED:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "train_tokens_per_s_per_chip"
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [CELL]
    (tokens,) = [m for m in bench["end_to_end"]
                 if m["name"] == "train_tokens_per_s_per_chip"]
    assert tokens["workloads"][-1] == CELL


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_file_names_a_reader_that_exists(name):
    spec = _json("benchmarks", "metrics", name + ".json")
    module, fn = spec["reader"].rsplit(".", 1)
    assert callable(getattr(importlib.import_module("benchmarks." + module),
                            fn))
    if "trace_query" in spec:
        re.compile(spec["trace_query"]["op"])


def test_the_share_query_takes_the_routed_blocks_shapes_only():
    q = re.compile(_json("benchmarks", "metrics",
                         "solar2_moe_held_time_share.json")
                   ["trace_query"]["op"])
    for shape in ("f32[8192,320]{1,0}", "s32[8192,8]{1,0}", "bf16[65536]{0}"):
        assert q.search(f"%fusion.1 = {shape} fusion(%a)"), shape
    assert q.search("%c.1 = (bf16[8192,4096]{1,0}) conditional(%p)")
    for shape in ("bf16[1,8192,4096]{2,1,0}", "bf16[64,8192,128]{2,1,0}",
                  "f32[8192,24576]{1,0}", "bf16[8192,8192]{1,0}"):
        assert not q.search(f"%fusion.1 = {shape} fusion(%a)"), shape


def test_rehearsal_runs_the_cells_files_and_its_kind():
    """The cell's command at the rehearsal sizes on the CPU, traced: exit
    3, the `train_kda` kind's comparison beside the loss (one KDA call
    against `reference_solar2.recurrence` on three inputs:
    benchmarks/train_kda_cell.py) in `checks` and within the cell's limits,
    every metric of the cell given a reader."""
    from benchmarks import train_kda_cell

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2640003333", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 3, done.stderr[-2000:]
    rehearsal = json.loads(done.stdout.strip().splitlines()[-1])["rehearsal"]
    assert rehearsal["failed"] == 0
    checks = rehearsal["checks"]
    # at 64 channels a bf16 activation's rounding is not averaged away as
    # at 4,096: 3e-4 is the chip's limit at the published widths
    assert checks["loss_rel_err"] < 1e-3
    assert checks["warmup_losses"][-1] < checks["warmup_losses"][0]
    assert train_kda_cell.within_limits(checks)
    assert set(checks["kda_errors"]) == set(train_kda_cell.LIMITS)
    assert rehearsal["counts"]["compiles_in_window"] == 0
    bench = _json("BENCHMARK.json")
    assert sorted(rehearsal["metric_was_readable"]) == sorted(
        m["name"] for m in bench["per_layer"]
        if CELL in m.get("workloads", [CELL]))
