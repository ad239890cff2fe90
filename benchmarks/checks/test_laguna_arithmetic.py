"""Checks of the window-attention cell's files, arithmetic and readers;
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

from benchmarks import moe_readers, opcount_laguna, peaks, window_readers  # noqa: E402

CELL = "train-laguna-1chip"
CONFIG = "laguna-xs.2-train-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("swa_flash_fwd_roofline", "swa_flash_bwd_roofline",
       "swa_attention_time_share", "laguna_full_attention_time_share",
       "laguna_moe_held_time_share")


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
    """Every key of the catalog's `config` under the same name and value
    (the three per-layer lists and `rope_parameters` whole), but the three
    that are the chip's share or the depth; no width among them; the floors
    of a `model_config` PR; every reading the config leaves open under
    `assumed`."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2"]
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
    held = config["layers_held"]
    assert config["num_hidden_layers"] == len(held)
    # floors: a whole period and >= 4 layers after the leading dense one,
    # >= 8 experts, >= 1/8 of the vocabulary
    assert held == list(range(held[-1] + 1)) and len(held) - 1 >= 4
    kinds = [config["layer_types"][i] for i in held[1:5]]
    assert kinds == ["sliding_attention"] * 3 + ["full_attention"]
    assert config["num_experts"] >= 8
    for item in ("gating", "router_scores", "qk_norm", "hidden_act",
                 "shared_expert", "rope", "weights", "router_on_a_share"):
        assert item in config["assumed"], item
    for alternative in ("gating", "router_scores", "qk_norm"):
        assert "Alternative" in config["assumed"][alternative]


def test_parameter_count_against_hand_sums_and_init(model):
    # q and o at H heads, k and v at 8, the gate per head, of 128 channels
    full = 2 * 2048 * 128 * (48 + 8) + 2048 * 48
    sliding = 2 * 2048 * 128 * (64 + 8) + 2048 * 64
    assert (full, sliding) == (29_458_432, 37_879_808)
    assert opcount_laguna.attention_params(model, 48) == full
    assert opcount_laguna.attention_params(model, 64) == sliding
    router, one_expert = 2048 * 256, 3 * 2048 * 512
    assert (router, one_expert) == (524_288, 3_145_728)
    sparse = router + 32 * one_expert + one_expert + 2 * 2048
    assert opcount_laguna.layer_params(model, 64, "sparse") \
        == sliding + sparse == 142_217_216
    assert opcount_laguna.layer_params(model, 48, "sparse") \
        == full + sparse == 133_795_840
    assert opcount_laguna.layer_params(model, 48, "dense") \
        == full + 3 * 2048 * 8192 + 2 * 2048 == 79_794_176
    ends = 2 * 12_544 * 2048 + 2048
    assert ends == 51_382_272
    at = lambda layers: opcount_laguna.num_params(  # noqa: E731
        {**model, "layers": layers})
    assert at(list(range(5))) == 691_623_936      # the cell: one period
    assert at(list(range(9))) == 1_252_071_424    # ISSUE 47's first choice
    assert opcount_laguna.num_params(model) == at(model["layers"])
    whole = {**model, "layers": None, "n_experts_held": 256,
             "vocab_size": 100_352}
    assert opcount_laguna.num_params(whole) == 33_442_596_864
    # ... and what `init` makes, to the parameter
    import jax

    from ray_tpu.models import window_moe

    for fields in (model, {**model, "layers": list(range(9))}, whole):
        cfg = window_moe.WindowMoeConfig(**fields)
        shapes = jax.eval_shape(
            lambda: window_moe.init(cfg, jax.random.PRNGKey(0)))
        assert sum(a.size for a in jax.tree.leaves(shapes)) \
            == cfg.num_params() == opcount_laguna.num_params(fields)


def test_operation_counts_against_hand_sums(model):
    assert opcount_laguna.kept_scores(8192, 512) \
        == 512 * 513 // 2 + (8192 - 512) * 512 == 4_063_488
    assert opcount_laguna.kept_scores(8192) == 8192 * 8193 // 2 == 33_558_528
    assert opcount_laguna.kept_scores(300, 512) == 300 * 301 // 2
    from ray_tpu.ops.flash_attention import CAUSAL, SlidingWindow

    assert SlidingWindow(512).needed(8192, 8192) == 4_063_488
    assert CAUSAL.needed(8192, 8192) == 33_558_528
    flops = lambda *a: opcount_laguna.layer_forward_flops(  # noqa: E731
        model, 8192, *a)
    window = flops("sliding_attention", 64, "sparse")
    full = flops("full_attention", 48, "sparse")
    # QK^T and PV over 128 channels: ~496 keys a row x 64 heads, a causal
    # half of 8,192 x 48 heads
    assert round(window["scores"] / 1e6, 1) == 16.3
    assert round(full["scores"] / 1e6, 1) == 100.7
    assert window["projections"] == 2 * 37_879_808
    # router, shared expert, 8 x 32 / 256 = 1 held pair
    assert window["experts"] == full["experts"] == 2 * (
        524_288 + 2 * 3_145_728)
    assert flops("full_attention", 48, "dense")["dense"] == 2 * 50_331_648
    parts = opcount_laguna.forward_flops_by_part(model, 8192)
    total = opcount_laguna.forward_flops_per_token(model, 8192)
    assert total == sum(parts.values())
    assert parts["head"] == 2 * 2048 * 12_544
    share = {k: round(100 * v / total, 1) for k, v in parts.items()}
    if model["layers"] == list(range(5)):
        assert round(total / 1e6, 1) == 801.8
        assert share == {"head": 6.4, "projections": 43.0,
                         "scores_full": 25.1, "scores_window": 6.1,
                         "dense": 12.6, "experts": 6.8}
    # ISSUE 47's count at layers 0-8
    nine = {**model, "layers": list(range(9))}
    assert round(opcount_laguna.forward_flops_per_token(nine, 8192) / 1e6) \
        == 1292
    # a share's routers run forward only
    frozen = opcount_laguna.frozen_router_params(model)
    assert frozen == (len(model["layers"]) - 1) * 524_288
    assert opcount_laguna.train_flops_per_token(model, 8192) \
        == 3 * total - 4 * frozen
    assert opcount_laguna.frozen_router_params(
        {**model, "n_experts_held": 256}) == 0


def test_flash_bounds_at_the_cells_shape():
    peak = peaks.peaks("TPU v5 lite")
    ops, nbytes = opcount_laguna.swa_flash_fwd(1, 64, 8192, 128, 512, 8 / 64)
    assert ops == 2 * 2 * 64 * 4_063_488 * 128
    assert nbytes == 2 * 8192 * 128 * (2 * 64 + 2 * 8)
    fwd = opcount_laguna.bound_seconds(ops, nbytes, peak)
    assert fwd == ops / 197e12 > nbytes / 819e9          # compute-bound
    assert fwd == pytest.approx(0.676e-3, rel=1e-3)
    ops_b, nbytes_b = opcount_laguna.swa_flash_bwd(1, 64, 8192, 128, 512,
                                                   8 / 64)
    assert ops_b == 2 * ops and nbytes_b == 2 * nbytes
    # against a causal call over the same positions: 12.1% of the scores
    from benchmarks import opcount
    assert ops / opcount.flash_fwd(1, 64, 8192, 128)[0] == pytest.approx(
        4_063_488 / (8192 * 8192 / 2))


def _ctx(model, name, opcount="opcount_laguna"):
    return {"name": name, "model": model, "opcount": opcount,
            "device_kind": "TPU v5 lite",
            "traffic": _json("benchmarks", "traffic", "pretrain-8k-b1.json")}


@pytest.mark.parametrize("name, per_call", [
    ("swa_flash_fwd_roofline", 1), ("swa_flash_bwd_roofline", 2)])
def test_flash_roofline_reader_on_synthetic_queries(model, name, per_call):
    spec = _json("benchmarks", "metrics", name + ".json")
    ctx = _ctx(model, name)
    ops, _ = getattr(opcount_laguna, spec["opcount"])(
        1, 64, 8192, 128, 512, 1 / 8)
    bound = ops / 197e12
    calls = 4 * 3
    q = {"total_s": calls * 2 * bound, "count": calls * per_call,
         "dims": [1, 64, 8192, 128]}
    got = window_readers.flash_roofline(
        spec, {"trace": {"queries": {name: q}}}, ctx)
    assert got == pytest.approx(50.0, rel=1e-6)
    # another kernel's event, no event, no trace, or a model without a
    # window (the parent's cells): nothing, and no raise
    gmm = {"total_s": 1.0, "count": 3, "dims": [65536, 512]}
    for readings in ({"trace": {"queries": {name: gmm}}},
                     {"trace": {"queries": {name: None}}},
                     {"trace": {"queries": {}}}, {"trace": None}, {}):
        assert window_readers.flash_roofline(spec, readings, ctx) is None
    llama = {"d_model": 4096, "n_heads": 32, "n_kv_heads": 8, "d_head": 128}
    assert window_readers.flash_roofline(
        spec, {"trace": {"queries": {name: q}}},
        _ctx(llama, name, "opcount")) is None
    share = _json("benchmarks", "metrics", "swa_attention_time_share.json")
    assert moe_readers.op_time_share(
        share, {"trace": {"window_s": 2.0, "queries": {
            "swa_attention_time_share": {"total_s": 0.5}}}},
        _ctx(model, "swa_attention_time_share")) == 25.0


def test_queries_tell_the_window_calls_from_the_full_ones():
    fwd, bwd, swa, full, held = (re.compile(_json(
        "benchmarks", "metrics", n + ".json")["trace_query"]["op"])
        for n in NEW)
    tail = (' custom-call(%a, %b), custom_call_target="tpu_custom_call", '
            'backend_config={}')
    four = "bf16[1,{0},8192,128]{{3,2,1,0:T(8,128)(2,1)}}".format
    lse = "f32[1,{0},8192,1]{{3,2,1,0}}".format
    w_fwd = f"%swa.attend.36 = ({four(64)}, {lse(64)})" + tail
    w_fwd_loose = f"%jvp_swa.attend_.1 = ({four(64)}, {lse(64)})" + tail
    w_dq = f"%swa.attend.34 = {four(64)}" + tail
    w_dkv = f"%swa.attend.35 = ({four(64)}, {four(64)})" + tail
    f_fwd = f"%closed_call.77 = ({four(48)}, {lse(48)})" + tail
    f_fwd0 = f"%jvp__.1 = ({four(48)}, {lse(48)})" + tail
    f_dq = f"%checkpoint.9 = {four(48)}" + tail
    f_dkv = f"%checkpoint.8 = ({four(48)}, {four(48)})" + tail
    gmm = "%gmm.3 = bf16[16384,512]{1,0:T(8,128)(2,1)}" + tail
    tgmm = "%tgmm.1 = bf16[32,2048,512]{2,1,0:T(8,128)(2,1)}" + tail
    rows = "%row_tile.118 = bf16[16384,2048]{1,0:T(8,128)(2,1)}" + tail
    fusion = f"%fusion.7 = {four(64)} fusion(%p), kind=kLoop"
    windows, fulls = (w_fwd, w_fwd_loose, w_dq, w_dkv), (f_fwd, f_fwd0, f_dq,
                                                        f_dkv)
    others = (gmm, tgmm, rows, fusion)
    assert fwd.search(w_fwd) and fwd.search(w_fwd_loose)
    assert not any(fwd.search(x) for x in (w_dq, w_dkv) + fulls + others)
    assert bwd.search(w_dq) and bwd.search(w_dkv)
    assert not any(bwd.search(x) for x in (w_fwd, w_fwd_loose) + fulls
                   + others)
    assert all(swa.search(x) for x in windows)
    assert not any(swa.search(x) for x in fulls + others)
    assert all(full.search(x) for x in fulls)
    assert not any(full.search(x) for x in windows + others)
    # the routed block: the capacity switches, and the ops shaped by the
    # tokens x router outputs, x top-8, or a scalar a pair
    cond = "%conditional.4 = bf16[8192,2048]{1,0} conditional(%i, %a, %b)"
    logits = "%fusion.9 = f32[8192,256]{1,0} fusion(%p), kind=kOutput"
    topk = "%sort.2 = (f32[8192,8]{1,0}, s32[8192,8]{1,0}) sort(%a, %b)"
    pairs = "%sort.5 = (s32[65536]{0}, s32[65536]{0}) sort(%a, %b)"
    assert all(held.search(x) for x in (cond, logits, topk, pairs))
    assert not any(held.search(x) for x in windows + fulls + (gmm, fusion))


def test_benchmark_json_lists_the_cell_where_its_readers_read():
    bench = _json("BENCHMARK.json")
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain-8k-b1", 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    traffic = _json("benchmarks", "traffic", "pretrain-8k-b1.json")
    assert (traffic["per_chip_batch"], traffic["seq"],
            traffic["batches_in_cycle"]) == (1, 8192, 8)
    listed = {m["name"]: m.get("workloads", [])
              for m in bench["per_layer"] + bench["end_to_end"]}
    for name in ("train_tokens_per_s_per_chip", "trainer_start_s",
                 "train_step_p50_ms", "train_stall_share",
                 "train_compiles_in_window", "train_mfu",
                 "device_idle_share.train", "peak_hbm_bytes.train",
                 "flash_unmasked_step_share", "moe_gmm_partial_tile_share",
                 "cluster_init_s", "gang_place_s", "gang_backend_init_s",
                 "gang_mesh_s", "gang_open_chip_s", "gang_session_launch_s",
                 "trainer_start_covered_share"):
        assert CELL in listed[name], name
    # their readers count one kind of head, a causal half, or other shapes
    for name in ("flash_fwd_roofline", "flash_bwd_roofline",
                 "mla_flash_fwd_roofline", "mla_flash_bwd_roofline",
                 "bd_flash_fwd_roofline", "moe_gmm_roofline",
                 "moe_dispatch_time_share", "moe_held_time_share",
                 "moe_combine_time_share", "tp_collective_time_share"):
        assert CELL not in listed[name], name
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)
    for m in mine:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_tokens_per_s_per_chip"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", m["name"] + ".json"))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_reference_is_independent_of_the_program():
    with open(os.path.join(ROOT, "benchmarks", "reference_laguna.py")) as f:
        source = f.read()
    imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", source, re.M)
    assert not [m for m in imports if m.startswith("ray_tpu")], imports


def test_rehearsal_runs_the_cells_files():
    """Exit 3, every listed metric's file loads and its reader runs. NOT
    asserted: `correct`. At the rehearsal's width (64) in bf16 1.4% to 5% of
    a layer's tokens choose another expert than the float32 reference's, and
    `loss_rel_err` reads 1.2e-4 to 3.7e-4 by the seed (JoyAI's rehearsal:
    0.3e-4 to 4.5e-4 over four seeds of the same sizes); in float32 the
    program is the reference to 7e-8 (tests/test_window_moe_reference.py)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2200000123", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert done.returncode == 3, done.stderr[-2000:]
    rehearsal = json.loads(done.stdout.strip().splitlines()[-1])["rehearsal"]
    assert rehearsal["failed"] == 0 and rehearsal["attempted"] > 0
    assert rehearsal["checks"]["loss_rel_err"] < 1e-3
    warm = rehearsal["checks"]["warmup_losses"]
    assert warm[-1] < warm[0]
    assert rehearsal["counts"]["compiles_in_window"] == 0
    readable = rehearsal["metric_was_readable"]
    bench = _json("BENCHMARK.json")
    assert sorted(readable) == sorted(
        m["name"] for m in bench["per_layer"]
        if CELL in m.get("workloads", [CELL]))
    # those that need a device trace, the Pallas lowerings' counters or the
    # chip's peaks say so and do not raise
    needs_chip = {"train_mfu", "device_idle_share.train",
                  "flash_unmasked_step_share", "moe_gmm_partial_tile_share",
                  *NEW}
    for name, was in readable.items():
        assert was is True or name in needs_chip, (name, was)
