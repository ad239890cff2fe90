"""Checks of the state-space cell's files (`train-nemotron3-1chip`: Mamba-2
layers, experts in a latent, GQA attention, an MTP block), its arithmetic,
readers and queries; a minute on the CPU, no chip:

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

from benchmarks import opcount_nemotron3 as opcount  # noqa: E402
from benchmarks import peaks, readers, ssd_readers  # noqa: E402

CELL = "train-nemotron3-1chip"
CONFIG = "nemotron-3-super-120b-a12b-train-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = ("ssd_fwd_roofline", "ssd_bwd_roofline", "ssd_time_share",
               "nemotron_moe_held_time_share")


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
                  if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"]
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    published = config["deployment"]["published"]
    assert published == {k: row["config"][k] for k in config["reduced"]}
    chips = config["deployment"]["chips_sharing_a_layer"]
    assert chips == 64
    assert config["n_routed_experts"] * chips == published["n_routed_experts"]
    assert config["router_outputs"] == published["n_routed_experts"]
    # floors: a whole period (a `*` and the pairs up to the next one) and
    # >= 4 layers, >= 8 experts, >= 1/8 of the vocabulary
    held, pattern = config["layers_held"], config["hybrid_override_pattern"]
    assert len(pattern) == published["num_hidden_layers"] == 88
    assert len(held) == config["num_hidden_layers"] >= 4
    assert held == list(range(25, 36))
    assert "".join(pattern[i] for i in held) == "*EMEMEMEMEM"
    assert pattern[36] == "*"
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 == published["vocab_size"]


def test_parameters_against_the_issues_table_and_the_program(model):
    """ISSUE 43's table, to the parameter: 1,378,724,736."""
    d = 4096
    mamba = d * 18_560 + 5 * 10_240 + 3 * 128 + 8_192 + 8_192 * d + d
    experts = (d * 512 + 512 + 2 * d * 1024 + 2 * d * 5376
               + 8 * 2 * 1024 * 2688 + d)
    attn = 2 * d * 4096 + 2 * d * 256 + d
    assert (mamba, experts, attn) == (109_640_064, 98_570_752, 35_655_680)
    for kind, n in (("M", mamba), ("E", experts), ("*", attn)):
        assert opcount.layer_params(model, kind) == n
    want = (attn + 5 * (experts + mamba) + 2 * 16_384 * d + d
            + 2 * d * d + 3 * d + attn + experts)
    assert opcount.num_params(model) == want == 1_378_724_736
    from ray_tpu.models import nemotron_h

    cfg = nemotron_h.NemotronHConfig(**model)
    assert cfg.num_params() == want
    assert cfg.plan() == [("one", "*", 25), ("pairs", 26, 5)]
    assert cfg.rope_theta == 0 and cfg.held == (0, 8)


def test_operation_counts_against_hand_sums(model):
    chunk = 2 * 128 * 128 * 128 + 16 * (2 * 128 * 128 * 64
                                        + 4 * 128 * 128 * 64)
    assert opcount.ssd_chunk_ops(16, 64, 128) == chunk == 104_857_600
    d = 4096
    mamba = 2 * (d * 18_560 + 8_192 * d + 4 * 10_240) + 8 * chunk / 128
    attn = 2 * (2 * d * 128 * 34) + 2 * 32 * 128 * 2048
    # router, latent in and out, the shared expert, 22 x 8 / 512 held pairs
    experts = 2 * (d * 512 + 2 * d * 1024 + 2 * d * 5376
                   + 22 * 8 / 512 * 2 * 1024 * 2688)
    head = 2 * d * 16_384
    forward = (head + attn + 5 * (experts + mamba)
               + 2 * 2 * d * d + head + attn + experts)
    assert opcount.forward_flops_per_token(model, 2048) \
        == pytest.approx(forward, rel=1e-12)
    frozen = 6 * d * 512
    assert opcount.frozen_router_params(model) == frozen
    got = opcount.train_flops_per_token(model, 2048)
    assert got == pytest.approx(3 * forward - 4 * frozen, rel=1e-12)
    assert round(forward / 1e9, 3) == 2.318 and round(got / 1e9, 3) == 6.903
    # what the configuration's `why_reduced` says: the two heads' share,
    # the MTP block's, the Mamba-2 layers'
    assert round(100 * 2 * head / forward, 1) == 11.6
    assert round(100 * (2 * 2 * d * d + head + attn + experts) / forward,
                 1) == 17.4
    assert round(100 * 5 * mamba / forward, 1) == 48.7
    # at 88 layers + the MTP block over the same slice of the vocabulary
    whole = dict(model, layers=None)
    full = opcount.forward_flops_per_token(whole, 2048)
    assert round(100 * 2 * head / full, 1) == 1.8


def test_kernel_bounds_at_the_cells_shape():
    peak = peaks.peaks("TPU v5 lite")
    ops, nbytes = opcount.ssd_fwd(2, 128, 2048, 64, 8, 128)
    assert ops == 2 * 8 * 16 * opcount.ssd_chunk_ops(16, 64, 128)
    assert nbytes == (2 * 2 * 2048 * (2 * 8192 + 2 * 1024)
                      + 2 * 4 * 2 * 2048 * 128 + 4 * 2 * 128 * 64 * 128)
    fwd = opcount.bound_seconds(ops, nbytes, peak)
    assert fwd == nbytes / 819e9 > ops / 197e12          # memory-bound
    assert fwd == pytest.approx(0.199e-3, rel=5e-3)
    assert ops / 197e12 == pytest.approx(0.136e-3, rel=5e-3)
    ops_b, nbytes_b = opcount.ssd_bwd(2, 128, 2048, 64, 8, 128)
    assert ops_b == 2 * ops and nbytes_b > nbytes
    bwd = opcount.bound_seconds(ops_b, nbytes_b, peak)
    assert bwd == nbytes_b / 819e9 == pytest.approx(0.297e-3, rel=5e-3)
    assert ops_b / 197e12 == pytest.approx(0.272e-3, rel=5e-3)
    from benchmarks import opcount as llama_opcount
    assert opcount.flash_fwd is llama_opcount.flash_fwd


def _ctx(model, name, opcount_module="opcount_nemotron3"):
    return {"name": name, "model": model, "opcount": opcount_module,
            "device_kind": "TPU v5 lite",
            "traffic": _json("benchmarks", "traffic", "pretrain-2k-b2.json")}


def test_the_traffic_is_pretrain_2k_at_half_the_batch():
    ours = _json("benchmarks", "traffic", "pretrain-2k-b2.json")
    theirs = _json("benchmarks", "traffic", "pretrain-2k.json")
    differ = {k for k in theirs if ours[k] != theirs[k]}
    assert differ == {"per_chip_batch", "note"} and set(ours) == set(theirs)
    assert (ours["per_chip_batch"], theirs["per_chip_batch"]) == (2, 4)


def test_ssd_roofline_reader_on_synthetic_queries(model):
    name = "ssd_fwd_roofline"
    spec = _json("benchmarks", "metrics", name + ".json")
    ctx = _ctx(model, name)
    bound = opcount.ssd_fwd(2, 128, 2048, 64, 8, 128)[1] / 819e9
    q = {"total_s": 20 * 4 * bound, "count": 20, "dims": [2, 2048, 8192]}
    got = ssd_readers.kernel_roofline(
        spec, {"trace": {"queries": {name: q}}}, ctx)
    assert got == pytest.approx(25.0, rel=1e-6)
    # the backward pass is two events a call, the states' walk first
    name_b = "ssd_bwd_roofline"
    spec_b = _json("benchmarks", "metrics", name_b + ".json")
    bound_b = opcount.ssd_bwd(2, 128, 2048, 64, 8, 128)[1] / 819e9
    for dims in ([16, 2048, 1024], [2, 2048, 8192]):
        q_b = {"total_s": 10 * 5 * bound_b, "count": 20, "dims": dims}
        assert ssd_readers.kernel_roofline(
            spec_b, {"trace": {"queries": {name_b: q_b}}},
            _ctx(model, name_b)) == pytest.approx(20.0, rel=1e-6)
    flash = {"total_s": 1.0, "count": 3, "dims": [2, 32, 2048, 128]}
    odd = {"total_s": 1.0, "count": 3, "dims": [128, 2048, 128]}
    for readings in ({"trace": {"queries": {name: flash}}},
                     {"trace": {"queries": {name: odd}}},
                     {"trace": {"queries": {name: None}}},
                     {"trace": {"queries": {}}}, {"trace": None}, {}):
        assert ssd_readers.kernel_roofline(spec, readings, ctx) is None
    # the parent's cells have no such field: nothing is read, nothing raises
    llama = {"d_model": 4096, "n_heads": 32, "n_kv_heads": 8, "d_head": 128}
    assert ssd_readers.kernel_roofline(
        spec, {"trace": {"queries": {name: q}}},
        _ctx(llama, name, "opcount")) is None


def test_the_accepted_flash_reader_reads_the_gqa_call(model):
    """`flash_fwd_roofline` (readers.kernel_roofline) wants `n_heads` and
    `n_kv_heads`: 32 / 2 here, 16 query heads to a KV head."""
    name = "flash_fwd_roofline"
    spec = _json("benchmarks", "metrics", name + ".json")
    ops, nbytes = opcount.flash_fwd(2, 32, 2048, 128, 2 / 32)
    bound = max(ops / 197e12, nbytes / 819e9)
    q = {"total_s": 8 * 2 * bound, "count": 8, "dims": [2, 32, 2048, 128]}
    assert readers.kernel_roofline(
        spec, {"trace": {"queries": {name: q}}},
        _ctx(model, name)) == pytest.approx(50.0, rel=1e-6)


# one event of every Pallas kernel the step has, as the xplane names them
_TAIL = (' custom-call(%a, %b), custom_call_target="tpu_custom_call", '
         'backend_config={}')
KERNEL_EVENTS = {
    "ssd_fwd": ("%ssd.scan.7 = (bf16[2,2048,8192]{2,1,0:T(8,128)(2,1)S(1)}, "
                "f32[16,128,1024]{2,1,0:T(8,128)})" + _TAIL),
    "ssd_states": "%ssd.scan.8 = f32[16,2048,1024]{2,1,0:T(8,128)}" + _TAIL,
    "ssd_grads": ("%ssd.scan.9 = (bf16[2,2048,8192]{2,1,0:T(8,128)(2,1)}, "
                  "bf16[2,2048,1024]{2,1,0:T(8,128)(2,1)}, "
                  "bf16[2,2048,1024]{2,1,0:T(8,128)(2,1)}, "
                  "f32[16,2048,16]{2,1,0:T(8,128)S(1)}, "
                  "f32[16,2048,16]{2,1,0:T(8,128)}, "
                  "/*index=5*/f32[16,16,2048]{2,1,0:T(8,128)})" + _TAIL),
    "flash_fwd": ("%fwd.1 = (bf16[2,32,2048,128]{3,2,1,0}, "
                  "f32[2,32,2048,1]{3,2,1,0})" + _TAIL),
    "flash_dq": "%dq.2 = bf16[2,32,2048,128]{3,2,1,0:T(8,128)(2,1)}" + _TAIL,
    "flash_dkv": ("%dkv.3 = (bf16[2,32,2048,128]{3,2,1,0}, "
                  "bf16[2,32,2048,128]{3,2,1,0})" + _TAIL),
    "gmm": "%gmm.3 = bf16[2816,2688]{1,0:T(8,128)(2,1)}" + _TAIL,
    "tgmm": "%tgmm.1 = bf16[8,1024,2688]{2,1,0:T(8,128)(2,1)}" + _TAIL,
    "row_sums": "%tgmm.9 = bf16[16,256,1024]{2,1,0:T(8,128)(2,1)}" + _TAIL,
}
# the kernels each KERNEL query the cell is listed under is for; every
# other kernel's event has to slip through it
QUERY_TAKES = {
    "ssd_fwd_roofline": {"ssd_fwd"},
    "ssd_bwd_roofline": {"ssd_states", "ssd_grads"},
    "ssd_time_share": {"ssd_fwd", "ssd_states", "ssd_grads"},
    "flash_fwd_roofline": {"flash_fwd"},
    "nemotron_moe_held_time_share": set(),
}


@pytest.mark.parametrize("metric", sorted(QUERY_TAKES))
def test_a_kernel_query_takes_its_kernels_and_no_other(metric):
    rx = re.compile(_json("benchmarks", "metrics", metric + ".json")[
        "trace_query"]["op"])
    took = {k for k, event in KERNEL_EVENTS.items() if rx.search(event)}
    assert took == QUERY_TAKES[metric]


def test_why_the_cell_is_not_under_the_flash_backward_query():
    """`flash_bwd_roofline` takes any Pallas call with one bf16 output: this
    cell's grouped matmuls too, as `train-olmoe-1chip`'s."""
    rx = re.compile(_json("benchmarks", "metrics", "flash_bwd_roofline.json")[
        "trace_query"]["op"])
    took = {k for k, event in KERNEL_EVENTS.items() if rx.search(event)}
    assert {"flash_dq", "flash_dkv", "gmm", "tgmm", "row_sums"} == took


def test_the_routed_blocks_query_takes_its_shapes():
    rx = re.compile(_json("benchmarks", "metrics",
                          "nemotron_moe_held_time_share.json")[
                              "trace_query"]["op"])
    for event in ("%sort.5 = (f32[4096,512]{1,0}, s32[4096,512]{1,0}) sort(",
                  "%fusion.9 = f32[4096,512]{1,0:T(8,128)} fusion(%a)",
                  "%fusion.3 = s32[4096,22]{1,0} fusion(%a)",
                  "%sort.1 = (s32[90112]{0}, s32[90112]{0}) sort(%a)",
                  "%conditional.4 = (bf16[4096,1024]{1,0}) conditional(%i)"):
        assert rx.search(event), event
    for event in ("%fusion.1 = bf16[4096,1024]{1,0} fusion(%a)",
                  "%fusion.2 = f32[2,2048]{1,0} fusion(%a)",
                  "%fusion.7 = bf16[4096,5376]{1,0} fusion(%a)"):
        assert not rx.search(event), event


def test_benchmark_json_lists_the_cell_where_its_readers_read():
    bench = _json("BENCHMARK.json")
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain-2k-b2", 1)
    assert bench["workloads"][-1] is cell and bench["configs"][-1]["name"] \
        == CONFIG
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    listed = {m["name"]: m.get("workloads", [])
              for m in bench["per_layer"] + bench["end_to_end"]}
    for name in ("train_tokens_per_s_per_chip", "train_mfu",
                 "train_step_p50_ms", "device_idle_share.train",
                 "peak_hbm_bytes.train", "flash_fwd_roofline",
                 "flash_unmasked_step_share",
                 "moe_gmm_partial_tile_share") + NEW_METRICS:
        assert CELL in listed[name], name
    # other cells' shapes and fields, or (flash_bwd) a query that would take
    # this cell's grouped matmuls
    for name in ("flash_bwd_roofline", "moe_gmm_roofline",
                 "moe_dispatch_time_share", "moe_held_time_share",
                 "kda_fwd_roofline", "kda_bwd_roofline", "kda_time_share",
                 "ling_moe_held_time_share", "mla_flash_fwd_roofline",
                 "moe_combine_time_share", "bd_attention_time_share"):
        assert CELL not in listed[name], name
    for name in NEW_METRICS:
        assert listed[name] == [CELL]
    assert [m["name"] for m in bench["per_layer"]][-4:] == list(NEW_METRICS)
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_file_names_a_reader_that_exists(name):
    spec = _json("benchmarks", "metrics", name + ".json")
    module, fn = spec["reader"].rsplit(".", 1)
    assert callable(getattr(
        importlib.import_module("benchmarks." + module), fn))
    if "opcount" in spec:
        assert callable(getattr(opcount, spec["opcount"]))
    if "trace_query" in spec:
        re.compile(spec["trace_query"]["op"])


def test_rehearsal_runs_the_cells_files():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2200430001", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 3, done.stderr[-2000:]
    (line,) = [ln for ln in done.stdout.splitlines()
               if ln.startswith('{"rehearsal"')]
    r = json.loads(line)["rehearsal"]
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 2
    assert r["checks"]["loss_rel_err"] <= 3e-4
    # off the chip the trace reads nothing
    readable = {k for k, v in r["metric_was_readable"].items() if v is True}
    assert "train_step_p50_ms" in readable
    assert not readable & (set(NEW_METRICS) | {"flash_fwd_roofline"})
