"""Checks of the four-stream cell's files, arithmetic and readers; two
minutes on the CPU, no chip:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/checks -q

Every entry of `BENCHMARK.json` is looked up by NAME, never by position, and
no list is pinned whole: a later PR that appends a cell or a metric leaves
these checks as they are.
"""

import json
import math
import os
import re
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import moe_readers, opcount_xing, peaks  # noqa: E402

CELL = "train-xing4-1chip"
CONFIG = "xing4.0-29b-a4b-train-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _named(entries, name):
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


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
    the nested `rope_scaling` whole, but the four that are the chip's share
    or the depth; no width among them; the floors of a `model_config` PR."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Xing4.0-29B-A4B"]
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == sorted(config["reduced"]) == [
        "first_k_dense_replace", "n_routed_experts", "num_hidden_layers",
        "vocab_size"]
    published = config["deployment"]["published"]
    assert published == {k: row["config"][k] for k in config["reduced"]}
    chips = config["deployment"]["chips_sharing_a_layer"]
    assert chips == 8
    assert config["n_routed_experts"] * chips == published["n_routed_experts"]
    assert config["vocab_size"] * chips == published["vocab_size"]
    assert config["router_outputs"] == published["n_routed_experts"]
    # the floors: a dense layer once + at least 4 expert layers, 8 experts,
    # an eighth of the vocabulary
    assert config["first_k_dense_replace"] == 1
    assert config["num_hidden_layers"] - 1 >= 4
    assert config["n_routed_experts"] >= 8
    assert (config["hc_mult"], config["hc_sinkhorn_iters"]) == (4, 20)
    for key in ("path_ends", "connection_norm", "maps", "mtp_streams",
                "rope", "weights", "router_on_a_share"):
        assert key in config["assumed"], key
    verdict = config["deployment"]["compiler_verdict"]
    depth = config["num_hidden_layers"]
    assert verdict[f"residuals_{depth}_layers"].startswith("placed")
    assert verdict[f"residuals_{depth + 1}_layers"].startswith("refused")


def test_the_parameter_count_of_the_cut_to_the_parameter(model):
    """By hand from the widths, the program's `num_params` and the
    configuration's text."""
    d, n = 3584, 4
    mla = (d * 768 + 768 + 768 * 32 * 192 + d * (512 + 64) + 512
           + 512 * 32 * (128 + 128) + 32 * 128 * d)
    assert mla == 28_411_136
    connection = n * d * (n + n + n * n) + 3 + (n + n + n * n)
    assert connection == 344_091
    outside = mla + 2 * d + 2 * connection          # + the two layer norms
    expert = 3 * d * 1024
    assert expert == 11_010_048
    dense_layer = outside + 3 * d * 9216
    expert_layer = outside + d * 64 + 64 + expert * (8 + 1)
    mtp = expert_layer + 2 * d * d + 3 * d
    total = 2 * 16384 * d + d + dense_layer + 8 * expert_layer + mtp
    assert total == 1_427_179_100
    from ray_tpu.models import mla_moe

    assert mla_moe.MlaMoeConfig(**model).num_params() == total
    assert "1,427,179,100 parameters" in _json(
        "benchmarks", "configs", CONFIG + ".json")["why_reduced"]
    # bf16 weights and two bf16 AdamW moments
    assert 6 * total / 2**30 == pytest.approx(7.975, abs=0.001)


def test_hc_bytes_and_the_counted_operations_by_hand(model):
    tokens = 4 * 2048
    # X read, X' written (4 streams each), h and y once; cotangents likewise
    assert opcount_xing.hc_bytes(model, tokens) \
        == 2 * (4 + 4 + 1 + 1) * tokens * 3584 * 2 == 1_174_405_120
    assert opcount_xing.hc_connections(model) == 2 * (9 + 1)
    bound_ms = 1e3 * opcount_xing.hc_bytes(model, tokens) / 819e9
    assert bound_ms == pytest.approx(1.434, abs=0.001)
    # a connection, forward, a token: norm + projection + pre + post
    by_hand = 2 * (14336 + 14336 * 24 + 14336 + 20 * 3584)
    assert opcount_xing.hc_flops_per_token(model) == by_hand == 888_832
    # the layer is JoyAI's count at this model's widths
    mla = (3584 * 768 + 768 * 32 * 192 + 3584 * 576 + 512 * 32 * 256
           + 32 * 128 * 3584)
    expert = mla + 3584 * 64 + (1 + 4 * 8 / 64) * 3 * 3584 * 1024
    dense = mla + 3 * 3584 * 9216
    head = 3584 * 16384
    active = dense + 8 * expert + head + (2 * 3584 ** 2 + expert + head)
    assert opcount_xing.active_matmul_params(model) == active
    attn = 32 * 2048 * (192 + 128)
    routers = 9 * 3584 * 64
    want = 3 * (2 * active + 10 * attn) - 4 * routers + 3 * 20 * by_hand
    assert opcount_xing.train_flops_per_token(model, 2048) == want
    # the path's ops are small beside the layer's, and they are counted
    assert 0.005 < 3 * 20 * by_hand / want < 0.02
    # the MLA call is JoyAI's: the accepted reader's names and signatures
    ops, nbytes = opcount_xing.flash_fwd(4, 32, 2048, 192, 128)
    assert ops == 2 * 4 * 32 * 2048 * 2048 * 320 / 2
    assert opcount_xing.flash_bwd(4, 32, 2048, 192, 128)[0] == 2 * ops
    assert opcount_xing.bound_seconds(ops, nbytes, peaks.peaks(
        "TPU v5 lite")) == ops / 197e12


def test_yarn_scale_of_the_published_group(config):
    g = config["rope_scaling"]
    assert (g["factor"], g["original_max_position_embeddings"],
            g["beta_fast"], g["beta_slow"]) == (64, 4096, 32, 1)
    from ray_tpu.models import mla_moe

    scale = mla_moe.MlaMoeConfig(rope_scaling=g).attn_scale
    assert scale == pytest.approx(
        192 ** -0.5 * (0.1 * g["mscale_all_dim"] * math.log(64) + 1) ** 2)
    assert f"{scale:.5f}" in config["assumed"]["rope"]


def test_the_readers_on_synthetic_readings(model):
    """The three shares are `moe_readers.op_time_share` of their own query's
    seconds over the traced window; nothing where the trace has no event."""
    ctx = {"model": model, "opcount": "opcount_xing",
           "device_kind": "TPU v5 lite",
           "traffic": {"per_chip_batch": 4, "seq": 2048}}
    for name in ("hc_time_share", "xing_moe_held_time_share",
                 "xing_moe_combine_time_share"):
        spec = _json("benchmarks", "metrics", name + ".json")
        assert spec["reader"] == "moe_readers.op_time_share"
        readings = {"trace": {"window_s": 3.0, "queries": {
            name: {"total_s": 0.9, "count": 5000, "dims": [4, 8192]}}}}
        assert moe_readers.op_time_share(
            spec, readings, dict(ctx, name=name)) == pytest.approx(30.0)
        assert moe_readers.op_time_share(
            spec, {"trace": {"window_s": 3.0, "queries": {}}},
            dict(ctx, name=name)) is None


def test_the_queries_take_the_events_they_are_for():
    hc = re.compile(_json("benchmarks", "metrics",
                          "hc_time_share.json")["trace_query"]["op"])
    held = re.compile(_json("benchmarks", "metrics",
                            "xing_moe_held_time_share.json")
                      ["trace_query"]["op"])
    path = [
        "%fusion.1 = bf16[4,4,2048,3584]{3,2,1,0:T(8,128)(2,1)} fusion(%p)",
        "%fusion.2 = (bf16[1,4,2048,3584]{3,2,1,0}, f32[4,2048]{1,0}) fusion(",
        "%fusion.2b = f32[4,4,2048,3584]{3,2,1,0:T(8,128)} fusion(%x)",
        "%divide_reduce_fusion.3 = f32[4,4,8192]{2,1,0:T(4,128)} fusion(%a)",
        "%fusion.4 = f32[4,8192]{1,0:T(4,128)} fusion(%a)",
        "%fusion.5 = f32[24,8192]{1,0} fusion(%a)",
        "%copy.6 = f32[4,4,4,2048,1]{3,2,1,0,4} copy(%a)",
        "%fusion.6b = f32[4,4,2048]{2,1,0:T(4,128)} fusion(%a)",
        "%fusion.6c = f32[16,8192]{1,0:T(8,128)} fusion(%a)",
        '%hc = bf16[8192,3584]{1,0} custom-call(%a), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(step)/hc.connect/x"}',
    ]
    other = [
        "%fusion.7 = bf16[4,2048,3584]{2,1,0:T(8,128)(2,1)} fusion(%p)",
        "%fusion.8 = f32[4,2048]{1,0:T(4,128)} fusion(%p)",
        "%fusion.9 = bf16[4,2048,32,128]{3,2,1,0} fusion(%p)",
        "%fusion.10 = f32[8192,64]{1,0} fusion(%p)",
        "%fusion.11 = s32[32768]{0} fusion(%p)",
        "%conditional.12 = (bf16[8192,3584]{1,0}) conditional(%i, %a, %b)",
        '%gmm = bf16[4096,1024]{1,0} custom-call(%a), custom_call_target='
        '"tpu_custom_call"',
        "%fusion.13 = bf16[16384,3584]{1,0} fusion(%p)",
        "%fusion.14 = bf16[8192,3584]{1,0} fusion(%p)",
        "%fusion.15 = f32[8192]{0} fusion(%p)",
    ]
    assert all(hc.search(x) for x in path)
    assert not any(hc.search(x) for x in other)
    assert [bool(held.search(x)) for x in other] == [
        False, False, False, True, True, True, False, False, False, False]
    assert not any(held.search(x) for x in path)
    combine = re.compile(_json("benchmarks", "metrics",
                               "xing_moe_combine_time_share.json")
                         ["trace_query"]["op"])
    tgmm = '%tgmm = bf16[{},256,3584]{{2,1,0}} custom-call(%a), ' \
        'custom_call_target="tpu_custom_call"'
    assert combine.search(tgmm.format(32))
    # the embedding's gradient's sorted sum: 16,384 rows, not the combine
    assert not combine.search(tgmm.format(64))
    assert not any(combine.search(x) for x in path + other)


def test_the_cell_is_listed_where_its_metrics_are_read():
    bench = _json("BENCHMARK.json")
    cell = _named(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain-2k", 1)
    entry = _named(bench["configs"], CONFIG)
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert sorted(entry["reduced"]) == sorted(_json(
        "benchmarks", "configs", CONFIG + ".json")["reduced"])
    assert CELL in _named(bench["end_to_end"],
                          "train_tokens_per_s_per_chip")["workloads"]
    for name in ("train_mfu", "train_step_p50_ms", "device_idle_share.train",
                 "peak_hbm_bytes.train", "mla_flash_fwd_roofline",
                 "mla_flash_bwd_roofline", "moe_gmm_partial_tile_share",
                 "flash_unmasked_step_share", "ce_fused_chunk_share",
                 "flash_triangle_step_share",
                 "embed_grad_sorted_row_share", "hc_time_share",
                 "xing_moe_held_time_share", "xing_moe_combine_time_share"):
        metric = _named(bench["per_layer"], name)
        assert CELL in metric.get("workloads", [CELL]), name
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", name + ".json"))
    # their queries go by D 2,048 or by other cells' shapes
    for name in ("moe_combine_time_share", "moe_held_time_share",
                 "flash_fwd_roofline", "moe_gmm_roofline"):
        assert CELL not in _named(bench["per_layer"], name)["workloads"], name
    for name in ("hc_time_share", "xing_moe_held_time_share",
                 "xing_moe_combine_time_share"):
        metric = _named(bench["per_layer"], name)
        assert metric["moves"] == "train_tokens_per_s_per_chip"
        assert (metric["unit"], metric["source"]) == ("%", "device_trace")


def test_rehearsal_runs_the_cells_files():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2610003333", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 3, done.stderr[-2000:]
    rehearsal = json.loads(done.stdout.strip().splitlines()[-1])["rehearsal"]
    assert rehearsal["failed"] == 0
    checks = rehearsal["checks"]
    # at 64 channels a bf16 stream's rounding is not averaged away as at
    # 3,584: the loss stands within 1e-3 of the reference's (3e-4 is the
    # chip's limit at the published widths) and falls in warm-up
    assert checks["loss_rel_err"] < 1e-3
    assert checks["warmup_losses"][-1] < checks["warmup_losses"][0]
    # the `train_hc` kind: one connection against the reference's, in
    # `correct` beside the loss (benchmarks/train_hc_cell.py)
    assert rehearsal["correct"] is True
    assert checks["hc_maps_err"] < 1e-5
    assert checks["hc_value_err"] < 1e-2 and checks["hc_grad_err"] < 1e-2
    assert rehearsal["counts"]["compiles_in_window"] == 0
    readable = rehearsal["metric_was_readable"]
    bench = _json("BENCHMARK.json")
    assert sorted(readable) == sorted(
        m["name"] for m in bench["per_layer"]
        if CELL in m.get("workloads", [CELL]))
    needs_chip = {"train_mfu", "device_idle_share.train",
                  "mla_flash_fwd_roofline", "mla_flash_bwd_roofline",
                  "hc_time_share", "xing_moe_held_time_share",
                  "xing_moe_combine_time_share", "flash_unmasked_step_share",
                  "flash_triangle_step_share",
                  "moe_gmm_partial_tile_share", "flash_kv_fetch_share",
                  "flash_vmem_stated_share"}
    for name, was in readable.items():
        assert was is True or name in needs_chip, (name, was)
