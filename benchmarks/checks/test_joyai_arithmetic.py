"""Checks of the latent-attention cell's files, arithmetic and readers;
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

from benchmarks import mla_readers, opcount_joyai, peaks  # noqa: E402

CELL = "train-joyai-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return _json("benchmarks", "configs", "joyai-llm-flash-train-1chip.json")


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
                  if r["name"] == "JoyAI-LLM-Flash"]
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    published = config["deployment"]["published"]
    assert published == {k: row["config"][k] for k in config["reduced"]}
    chips = config["deployment"]["chips_sharing_a_layer"]
    assert config["n_routed_experts"] * chips == published["n_routed_experts"]
    assert config["vocab_size"] * chips == published["vocab_size"]
    assert config["router_outputs"] == published["n_routed_experts"]
    # floors: >= 4 layers after the dense one, >= 8 experts, >= 1/8 vocab
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8


def test_operation_counts_against_hand_sums(model):
    # MLA: q down 2048x1536, q up 1536x32x192, kv down 2048x576,
    # kv up 512x32x256, o 4096x2048
    mla = (2048 * 1536 + 1536 * 32 * 192 + 2048 * 576 + 512 * 32 * 256
           + 4096 * 2048)
    assert mla == 26_345_472 == opcount_joyai.mla_matmul_params(model)
    one_expert = 3 * 2048 * 768
    # router 256 outputs, the shared expert, 8 x 32 / 256 = 1 held pair
    expert_layer = mla + 2048 * 256 + one_expert + 1.0 * one_expert
    assert opcount_joyai.expert_layer_active_matmul_params(model) \
        == expert_layer
    head = 2048 * 16160
    layers = model["n_layers"]
    want = (mla + 3 * 2048 * 7168 + (layers - 1) * expert_layer + head
            + 2 * 2048 * 2048 + expert_layer + head)
    assert opcount_joyai.active_matmul_params(model) == want
    # scores over 192 channels, values over 128, causal half, every MLA
    # layer and the MTP block's
    attn = 32 * 2048 * (192 + 128)
    got = opcount_joyai.train_flops_per_token(model, 2048)
    # a share's routers (the expert layers' and the MTP block's) are frozen:
    # forward only, their 2 x forward of a backward pass is not counted
    frozen = layers * 2048 * 256
    assert opcount_joyai.frozen_router_params(model) == frozen
    assert got == 3 * (2 * want + (layers + 1) * attn) - 2 * 2 * frozen
    assert opcount_joyai.frozen_router_params(
        {**model, "n_experts_held": model["n_experts"]}) == 0
    assert layers != 7 or round(got / 1e9, 3) == 2.883
    # per token and expert layer, forward: what the cell's `why` says
    assert round(2 * mla / 1e6, 1) == 52.7 and round(attn / 1e6) == 21
    assert round(2 * one_expert / 1e6, 1) == 9.4


def test_flash_bounds_at_the_cells_shape():
    peak = peaks.peaks("TPU v5 lite")
    ops, nbytes = opcount_joyai.flash_fwd(4, 32, 2048, 192, 128)
    assert ops == 2 * 4 * 32 * 2048 * 2048 * (192 + 128) / 2
    assert nbytes == 2 * 4 * 32 * 2048 * (2 * 192 + 2 * 128)
    fwd = opcount_joyai.bound_seconds(ops, nbytes, peak)
    assert fwd == ops / 197e12 > nbytes / 819e9          # compute-bound
    assert fwd == pytest.approx(0.872e-3, rel=1e-3)
    ops_b, nbytes_b = opcount_joyai.flash_bwd(4, 32, 2048, 192, 128)
    assert ops_b == 2 * ops and nbytes_b == 2 * nbytes
    # equal widths: opcount.py's own counts
    from benchmarks import opcount
    assert opcount_joyai.flash_fwd(4, 32, 2048, 128, 128) \
        == opcount.flash_fwd(4, 32, 2048, 128)
    assert opcount_joyai.flash_bwd(4, 32, 2048, 128, 128) \
        == opcount.flash_bwd(4, 32, 2048, 128)


def _ctx(model, name, opcount="opcount_joyai"):
    return {"name": name, "model": model, "opcount": opcount,
            "device_kind": "TPU v5 lite",
            "traffic": _json("benchmarks", "traffic", "pretrain-2k.json")}


@pytest.mark.parametrize("name, dims, per_call", [
    ("mla_flash_fwd_roofline", [4, 32, 2048, 128], 1),
    ("mla_flash_bwd_roofline", [4, 32, 2048, 192], 2)])
def test_flash_roofline_reader_on_synthetic_queries(model, name, dims,
                                                    per_call):
    spec = _json("benchmarks", "metrics", name + ".json")
    ctx = _ctx(model, name)
    ops, nbytes = getattr(opcount_joyai, spec["opcount"])(4, 32, 2048, 192, 128)
    bound = ops / 197e12
    calls = 4 * 8
    q = {"total_s": calls * 2 * bound, "count": calls * per_call, "dims": dims}
    got = mla_readers.flash_roofline(
        spec, {"trace": {"queries": {name: q}}}, ctx)
    assert got == pytest.approx(50.0, rel=1e-6)
    # another kernel's event (the grouped matmuls, a 128/128 flash call
    # has d 128 too and IS read: the widths are the model's), no event, no
    # trace, or a model without MLA's fields (the parent's cells): nothing
    gmm = {"total_s": 1.0, "count": 3, "dims": [10240, 768]}
    odd = {"total_s": 1.0, "count": 3, "dims": [4, 32, 2048, 64]}
    for readings in ({"trace": {"queries": {name: gmm}}},
                     {"trace": {"queries": {name: odd}}},
                     {"trace": {"queries": {name: None}}},
                     {"trace": {"queries": {}}}, {"trace": None}, {}):
        assert mla_readers.flash_roofline(spec, readings, ctx) is None
    llama = {"d_model": 4096, "n_heads": 32, "n_kv_heads": 8, "d_head": 128}
    assert mla_readers.flash_roofline(
        spec, {"trace": {"queries": {name: q}}},
        _ctx(llama, name, "opcount")) is None


def test_flash_queries_match_the_mla_kernels_and_no_grouped_matmul():
    fwd, bwd = (re.compile(_json("benchmarks", "metrics", n + ".json")[
        "trace_query"]["op"]) for n in ("mla_flash_fwd_roofline",
                                        "mla_flash_bwd_roofline"))
    tail = (' custom-call(%a, %b), custom_call_target="tpu_custom_call", '
            'backend_config={}')
    flash_fwd = ("%f.1 = (bf16[4,32,2048,128]{3,2,1,0}, "
                 "f32[4,32,2048,1]{3,2,1,0})" + tail)
    flash_dq = "%dq.1 = bf16[4,32,2048,192]{3,2,1,0:T(8,128)(2,1)}" + tail
    flash_dkv = ("%dkv.1 = (bf16[4,32,2048,192]{3,2,1,0}, "
                 "bf16[4,32,2048,128]{3,2,1,0})" + tail)
    gmm = "%gmm.3 = bf16[10240,768]{1,0:T(8,128)(2,1)}" + tail
    tgmm = "%tgmm.1 = bf16[32,2048,768]{2,1,0:T(8,128)(2,1)}" + tail
    fusion = "%fusion.7 = bf16[4,32,2048,192]{3,2,1,0} fusion(%p), kind=kLoop"
    assert fwd.search(flash_fwd)
    assert not any(fwd.search(x) for x in (flash_dq, flash_dkv, gmm, tgmm,
                                           fusion))
    assert bwd.search(flash_dq) and bwd.search(flash_dkv)
    assert not any(bwd.search(x) for x in (flash_fwd, gmm, tgmm, fusion))


def test_benchmark_json_lists_the_cell_where_its_readers_read():
    bench = _json("BENCHMARK.json")
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "joyai-llm-flash-train-1chip", "pretrain-2k", 1)
    listed = {m["name"]: m.get("workloads", [])
              for m in bench["per_layer"] + bench["end_to_end"]}
    for name in ("train_tokens_per_s_per_chip", "train_mfu",
                 "train_step_p50_ms", "device_idle_share.train",
                 "peak_hbm_bytes.train", "mla_flash_fwd_roofline",
                 "mla_flash_bwd_roofline", "moe_held_time_share"):
        assert CELL in listed[name], name
    # their readers want n_kv_heads and one width, or OLMoE's shapes
    for name in ("flash_fwd_roofline", "flash_bwd_roofline",
                 "moe_gmm_roofline", "moe_dispatch_time_share"):
        assert CELL not in listed[name], name
    for name in ("mla_flash_fwd_roofline", "mla_flash_bwd_roofline",
                 "moe_held_time_share"):
        assert listed[name] == [CELL]
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", name + ".json"))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_rehearsal_runs_the_cells_files():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2200003333", "--seconds", "3",
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
    needs_chip = {"train_mfu", "device_idle_share.train",
                  "mla_flash_fwd_roofline", "mla_flash_bwd_roofline",
                  "moe_held_time_share"}
    for name, was in readable.items():
        assert was is True or name in needs_chip, (name, was)
