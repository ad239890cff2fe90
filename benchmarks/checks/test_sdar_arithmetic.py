"""Checks of the block-diffusion cell's files, arithmetic and readers;
a minute on the CPU, no chip:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/checks -q
"""

import json
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import bd_readers, opcount_sdar, peaks  # noqa: E402

CELL = "train-sdar-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("bd_flash_fwd_roofline", "bd_flash_bwd_roofline",
       "bd_attention_time_share", "sdar_moe_held_time_share")


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _json("benchmarks", "configs", "sdar-30b-a3b-chat-train-1chip.json")


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
                  if r["name"] == "SDAR-30B-A3B-Chat"]
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    published = config["deployment"]["published"]
    assert published == {k: row["config"][k] for k in config["reduced"]}
    chips = config["deployment"]["chips_sharing_a_layer"]
    assert config["num_experts"] * chips == published["num_experts"]
    assert config["vocab_size"] * chips == published["vocab_size"]
    assert config["router_outputs"] == published["num_experts"]
    assert config["mask_token_id"] == config["vocab_size"] - 1
    # floors: >= 4 layers (no leading dense one), >= 8 experts, >= 1/8 vocab
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8
    for item in ("block_length", "noise_t", "noise_eps", "mask_token_id",
                 "router_aux_loss_coef", "objective"):
        assert item in config["assumed"], item


def test_parameter_count_against_hand_sums(model):
    # q and o 2048 x 32 x 128 each, k and v 2048 x 4 x 128 each
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512
    assert attention == 18_874_368
    router, one_expert = 2048 * 128, 3 * 2048 * 768
    assert (router, one_expert) == (262_144, 4_718_592)
    # two layer norms and the two [128] QK-norm scales
    layer = attention + router + 16 * one_expert + 2 * 2048 + 2 * 128
    assert layer == 94_638_336 == opcount_sdar.layer_params(model)
    ends = 2 * 18_992 * 2048
    assert ends == 77_791_232
    assert opcount_sdar.num_params(model) \
        == model["n_layers"] * layer + ends + 2048
    assert opcount_sdar.num_params({**model, "n_layers": 8}) == 834_899_968


def test_operation_counts_against_hand_sums(model):
    assert opcount_sdar.kept_scores(2048, 4) == 2048 * 2048 + 2048 * 4 \
        == 4_202_496
    # HALF of what a causal call over the 4,096-long concatenation keeps
    assert 2 * 4_202_496 == pytest.approx(4096 * 4097 / 2, rel=2e-3)
    # a row: attention, router, 8 x 16 / 128 = 1 held pair
    row = 18_874_368 + 262_144 + 1.0 * 4_718_592
    assert opcount_sdar.row_active_matmul_params(model) == row
    # a DATA token: two rows through a layer's matmuls, its share of the
    # kept scores (QK^T and PV over 128 channels, 32 heads)
    scores = 2 * 2 * 32 * 128 * 4_202_496 / 2048
    assert round(scores / 1e6, 1) == 33.6
    layer = 2 * 2 * row + scores
    assert round(layer / 1e6) == 129
    head = 2 * 2048 * 18_992            # one row: the x_t half alone
    layers = model["n_layers"]
    assert opcount_sdar.forward_flops_per_token(model, 2048) \
        == layers * layer + head
    assert opcount_sdar.train_flops_per_token(model, 2048) \
        == 3 * (layers * layer + head)
    # with every expert held a row is multiplied by its 8
    whole = {**model, "n_experts_held": None}
    assert opcount_sdar.row_active_matmul_params(whole) \
        == 18_874_368 + 262_144 + 8 * 4_718_592
    # the head's share of the counted ops, at the cell's depth and at 48
    share = head / (layers * layer + head)
    assert layers != 12 or round(100 * share, 1) == 4.8
    assert round(100 * head / (48 * layer + head), 1) == 1.2


def test_flash_bounds_at_the_cells_shape():
    peak = peaks.peaks("TPU v5 lite")
    ops, nbytes = opcount_sdar.bd_flash_fwd(4, 32, 4096, 128, 4, 4 / 32)
    assert ops == 2 * 2 * 4 * 32 * 4_202_496 * 128
    assert nbytes == 2 * 4 * 4096 * 128 * (2 * 32 + 2 * 4)
    fwd = opcount_sdar.bound_seconds(ops, nbytes, peak)
    assert fwd == ops / 197e12 > nbytes / 819e9          # compute-bound
    assert fwd == pytest.approx(1.398e-3, rel=1e-3)
    ops_b, nbytes_b = opcount_sdar.bd_flash_bwd(4, 32, 4096, 128, 4, 4 / 32)
    assert ops_b == 2 * ops and nbytes_b == 2 * nbytes
    # against a causal call over the same 4,096 positions: half the ops
    from benchmarks import opcount
    assert ops == pytest.approx(
        opcount.flash_fwd(4, 32, 4096, 128)[0] / 2, rel=2e-3)


def _ctx(model, name, opcount="opcount_sdar"):
    return {"name": name, "model": model, "opcount": opcount,
            "device_kind": "TPU v5 lite",
            "traffic": _json("benchmarks", "traffic", "pretrain-2k.json")}


@pytest.mark.parametrize("name, per_call", [
    ("bd_flash_fwd_roofline", 1), ("bd_flash_bwd_roofline", 2)])
def test_flash_roofline_reader_on_synthetic_queries(model, name, per_call):
    spec = _json("benchmarks", "metrics", name + ".json")
    ctx = _ctx(model, name)
    ops, _ = getattr(opcount_sdar, spec["opcount"])(4, 32, 4096, 128, 4, 1 / 8)
    bound = ops / 197e12
    calls = 4 * 12
    q = {"total_s": calls * 2 * bound, "count": calls * per_call,
         "dims": [4, 32, 4096, 128]}
    got = bd_readers.flash_roofline(
        spec, {"trace": {"queries": {name: q}}}, ctx)
    assert got == pytest.approx(50.0, rel=1e-6)
    # another kernel's event (the grouped matmuls), no event, no trace, or
    # a model without a block (the parent's cells): nothing, and no raise
    gmm = {"total_s": 1.0, "count": 3, "dims": [32768, 768]}
    for readings in ({"trace": {"queries": {name: gmm}}},
                     {"trace": {"queries": {name: None}}},
                     {"trace": {"queries": {}}}, {"trace": None}, {}):
        assert bd_readers.flash_roofline(spec, readings, ctx) is None
    llama = {"d_model": 4096, "n_heads": 32, "n_kv_heads": 8, "d_head": 128}
    assert bd_readers.flash_roofline(
        spec, {"trace": {"queries": {name: q}}},
        _ctx(llama, name, "opcount")) is None


def test_queries_match_the_flash_kernels_and_no_grouped_matmul():
    fwd, bwd, both, held = (re.compile(_json(
        "benchmarks", "metrics", n + ".json")["trace_query"]["op"])
        for n in NEW)
    tail = (' custom-call(%a, %b), custom_call_target="tpu_custom_call", '
            'backend_config={}')
    flash_fwd = ("%bd.attend.1 = (bf16[4,32,4096,128]{3,2,1,0}, "
                 "f32[4,32,4096,1]{3,2,1,0})" + tail)
    flash_dq = "%bd.attend.2 = bf16[4,32,4096,128]{3,2,1,0:T(8,128)(2,1)}" \
        + tail
    flash_dkv = ("%bd.attend.3 = (bf16[4,32,4096,128]{3,2,1,0}, "
                 "bf16[4,32,4096,128]{3,2,1,0})" + tail)
    gmm = "%gmm.3 = bf16[32768,768]{1,0:T(8,128)(2,1)}" + tail
    tgmm = "%tgmm.1 = bf16[16,2048,768]{2,1,0:T(8,128)(2,1)}" + tail
    fusion = "%fusion.7 = bf16[4,32,4096,128]{3,2,1,0} fusion(%p), kind=kLoop"
    assert fwd.search(flash_fwd)
    assert not any(fwd.search(x) for x in (flash_dq, flash_dkv, gmm, tgmm,
                                           fusion))
    assert bwd.search(flash_dq) and bwd.search(flash_dkv)
    assert not any(bwd.search(x) for x in (flash_fwd, gmm, tgmm, fusion))
    assert all(both.search(x) for x in (flash_fwd, flash_dq, flash_dkv))
    assert not any(both.search(x) for x in (gmm, tgmm, fusion))
    # the routed block: the capacity switches, and the ops shaped by the
    # processed rows x router outputs, x top-8, or a scalar a pair
    cond = "%conditional.4 = bf16[16384,2048]{1,0} conditional(%i, %a, %b)"
    logits = "%fusion.9 = f32[16384,128]{1,0} fusion(%p), kind=kOutput"
    topk = "%sort.2 = (f32[16384,8]{1,0}, s32[16384,8]{1,0}) sort(%a, %b)"
    pairs = "%sort.5 = (s32[131072]{0}, s32[131072]{0}) sort(%a, %b)"
    assert all(held.search(x) for x in (cond, logits, topk, pairs))
    assert not any(held.search(x) for x in (flash_fwd, gmm, fusion))


def test_benchmark_json_lists_the_cell_where_its_readers_read():
    bench = _json("BENCHMARK.json")
    assert bench["workloads"][-1]["name"] == CELL
    cell = bench["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b-chat-train-1chip", "pretrain-2k", 1)
    assert len(cell["why"]) <= 200 and len(bench["configs"][-1]["why"]) <= 200
    listed = {m["name"]: m.get("workloads", [])
              for m in bench["per_layer"] + bench["end_to_end"]}
    for name in ("train_tokens_per_s_per_chip", "trainer_start_s",
                 "train_step_p50_ms", "train_stall_share",
                 "train_compiles_in_window", "train_mfu",
                 "device_idle_share.train", "peak_hbm_bytes.train",
                 "cluster_init_s", "gang_place_s", "gang_backend_init_s",
                 "gang_mesh_s", "gang_open_chip_s", "gang_session_launch_s",
                 "trainer_start_covered_share"):
        assert listed[name][-1] == CELL, name
    # their readers count a causal half, OLMoE's or JoyAI's shapes
    for name in ("flash_fwd_roofline", "flash_bwd_roofline",
                 "mla_flash_fwd_roofline", "mla_flash_bwd_roofline",
                 "moe_gmm_roofline", "moe_dispatch_time_share",
                 "moe_held_time_share", "tp_collective_time_share"):
        assert CELL not in listed[name], name
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NEW)
    for m in bench["per_layer"][-4:]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_tokens_per_s_per_chip"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", m["name"] + ".json"))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_reference_is_independent_of_the_program():
    with open(os.path.join(ROOT, "benchmarks", "reference_sdar.py")) as f:
        source = f.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", source, re.M)
    assert not [m for m in imports if m.startswith("ray_tpu")], imports


def test_rehearsal_runs_the_cells_files():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2200000123", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 3, done.stderr[-2000:]
    rehearsal = json.loads(done.stdout.strip().splitlines()[-1])["rehearsal"]
    assert rehearsal["correct"] and rehearsal["failed"] == 0
    assert rehearsal["counts"]["compiles_in_window"] == 0
    readable = rehearsal["metric_was_readable"]
    bench = _json("BENCHMARK.json")
    assert sorted(readable) == sorted(
        m["name"] for m in bench["per_layer"]
        if CELL in m.get("workloads", [CELL]))
    # every listed metric's file loads and its reader runs; those that
    # need a device trace or the chip's peaks say so and do not raise
    needs_chip = {"train_mfu", "device_idle_share.train", *NEW}
    for name, was in readable.items():
        assert was is True or name in needs_chip, (name, was)
