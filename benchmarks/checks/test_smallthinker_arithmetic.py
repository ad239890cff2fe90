"""Checks of the SmallThinker cell's files, arithmetic and readers; a minute
on the CPU, no chip:

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

from benchmarks import (  # noqa: E402
    counter_readers,
    moe_readers,
    opcount_smallthinker,
    peaks,
    window_readers,
)

CELL = "train-smallthinker-1chip"
CONFIG = "smallthinker-21ba3b-train-1chip"
TRAFFIC = "pretrain-16k-b1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("full_flash_fwd_roofline", "full_flash_bwd_roofline",
       "smallthinker_full_attention_time_share",
       "smallthinker_moe_held_time_share", "flash_vmem_stated_share")
LENGTHENED = ("swa_flash_fwd_roofline", "swa_flash_bwd_roofline",
              "swa_attention_time_share", "moe_gmm_partial_tile_share")
S = 16384


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
    (the two layouts whole), but the three that are the chip's share or the
    depth; no width among them; the floors of a `model_config` PR; every
    reading the config leaves open under `assumed`, with its alternative
    where the program runs one."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "SmallThinker-21BA3B-Instruct"]
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == sorted(config["reduced"]) == [
        "moe_num_primary_experts", "num_hidden_layers", "vocab_size"]
    published = config["deployment"]["published"]
    assert published == {k: row["config"][k] for k in config["reduced"]}
    chips = config["deployment"]["chips_sharing_a_layer"]
    assert chips == 4
    assert config["moe_num_primary_experts"] * chips \
        == published["moe_num_primary_experts"] == config["router_outputs"]
    assert config["vocab_size"] * chips == published["vocab_size"]
    held = config["layers_held"]
    assert config["num_hidden_layers"] == len(held)
    # floors: a whole period (no leading dense layer: four layers in the
    # published order and ratio), >= 8 experts, >= 1/8 of the vocabulary
    assert held == [0, 1, 2, 3]
    assert [config["sliding_window_layout"][i] for i in held] == [0, 1, 1, 1]
    assert config["rope_layout"] == config["sliding_window_layout"] \
        == [int(i % 4 != 0) for i in range(52)]
    assert config["moe_num_primary_experts"] >= 8
    # the derived keys say what the published ones say
    assert config["num_attention_heads_per_layer"] \
        == [config["num_attention_heads"]] * 52
    assert config["mlp_layer_types"] == ["sparse"] * 52
    assert {g["rope_theta"] for g in config["rope_parameters"].values()} \
        == {config["rope_theta"]}
    for item in ("router_input", "window", "hidden_act", "router_scores",
                 "bias_qk_norm_gate", "rope", "aux_loss",
                 "secondary_experts", "weights", "router_on_a_share"):
        assert item in config["assumed"], item
    for alternative in ("router_input", "window", "hidden_act"):
        assert "Alternative" in config["assumed"][alternative]
    fields = config["program"]["fields"]
    assert (fields["router_input"], fields["expert_form"], fields["score"],
            fields["d_ff_shared"], fields["attn_gate"]) == (
        "attention_input", "reglu", "softmax", 0, False)


def test_parameter_count_against_hand_sums_and_init(model):
    """ISSUE 50's table: 21,140,480 outside the experts and 94,371,840 in
    the 16 held a layer, 194,480,640 at the ends, 656,529,920 held of the
    published 21,506,562,560."""
    attention = 2 * 2560 * 128 * (28 + 4)
    assert attention == 20_971_520 \
        == opcount_smallthinker.opcount_laguna.attention_params(model, 28)
    router, one_expert = 2560 * 64, 3 * 2560 * 768
    assert (router, one_expert) == (163_840, 5_898_240)
    assert attention + 2 * 2560 + router == 21_140_480
    layer = 21_140_480 + 16 * one_expert
    assert layer == 115_512_320
    ends = 2 * 37_984 * 2560 + 2560
    assert ends == 194_480_640
    assert opcount_smallthinker.num_params(model) \
        == 4 * layer + ends == 656_529_920
    assert 656_529_920 * 6 / 2**30 == pytest.approx(3.67, abs=0.005)
    whole = {**model, "layers": None, "n_experts_held": 64,
             "vocab_size": 151_936}
    assert opcount_smallthinker.num_params(whole) \
        == 52 * 398_627_840 + 777_914_880 == 21_506_562_560
    import jax

    from ray_tpu.models import window_moe

    for fields in (model, whole):
        cfg = window_moe.WindowMoeConfig(**fields)
        shapes = jax.eval_shape(
            lambda: window_moe.init(cfg, jax.random.PRNGKey(0)))
        assert sum(a.size for a in jax.tree.leaves(shapes)) \
            == cfg.num_params() == opcount_smallthinker.num_params(fields)
    cfg = window_moe.WindowMoeConfig(**model)
    assert cfg.plan() == ([], [0, 1, 2, 3], [], [("loose", 4)])
    assert cfg.rotary("full").theta == 0
    assert cfg.rotary("sliding").theta == 1_500_000


def test_operation_counts_against_hand_sums(model):
    """The parts' shares `why_reduced` and the cell's `why` state."""
    assert opcount_smallthinker.kept_scores(S, 4096) \
        == 4096 * 4097 // 2 + (S - 4096) * 4096 == 58_722_304
    assert opcount_smallthinker.kept_scores(S) == S * (S + 1) // 2 \
        == 134_225_920
    assert 58_722_304 / 134_225_920 == pytest.approx(0.4375, abs=1e-3)
    from ray_tpu.ops.flash_attention import CAUSAL, SlidingWindow

    assert SlidingWindow(4096).needed(S, S) == 58_722_304
    assert CAUSAL.needed(S, S) == 134_225_920
    parts = opcount_smallthinker.forward_flops_by_part(model, S)
    total = opcount_smallthinker.forward_flops_per_token(model, S)
    assert total == sum(parts.values())
    assert round(total / 1e6, 1) == 705.9
    mflop = {k: round(v / 1e6, 1) for k, v in parts.items()}
    assert mflop == {"head": 194.5, "projections": 167.8,
                     "scores_full": 117.4, "scores_window": 154.1,
                     "experts": 72.1}
    share = {k: round(100 * v / total, 1) for k, v in parts.items()}
    assert share == {"head": 27.5, "projections": 23.8, "scores_full": 16.6,
                     "scores_window": 21.8, "experts": 10.2}
    assert parts["projections"] == 4 * 2 * 20_971_520
    # router + 6 x 16 / 64 = 1.5 held pairs of three matrices, no shared
    assert parts["experts"] == 4 * 2 * (163_840 + 1.5 * 5_898_240)
    assert parts["head"] == 2 * 2560 * 37_984
    deep = opcount_smallthinker.forward_flops_by_part(
        {**model, "layers": None}, S)
    assert round(100 * deep["head"] / sum(deep.values()), 1) == 2.8
    # a held expert's rows a step, here and in the 4-chip deployment
    assert S * 6 // 64 == 1536 and 4 * S * 6 // 64 == 6144
    frozen = opcount_smallthinker.opcount_laguna.frozen_router_params(
        opcount_smallthinker.named(model))
    assert frozen == 4 * 163_840
    assert opcount_smallthinker.train_flops_per_token(model, S) \
        == 3 * total - 4 * frozen


def test_flash_bounds_at_the_cells_shape():
    peak = peaks.peaks("TPU v5 lite")
    for fwd, bwd, kept in (
            (opcount_smallthinker.swa_flash_fwd,
             opcount_smallthinker.swa_flash_bwd, 58_722_304),
            (opcount_smallthinker.full_flash_fwd,
             opcount_smallthinker.full_flash_bwd, 134_225_920)):
        ops, nbytes = fwd(1, 28, S, 128, 4096, 4 / 28)
        assert ops == 2 * 2 * 28 * kept * 128
        assert nbytes == 2 * S * 128 * (2 * 28 + 2 * 4)
        bound = opcount_smallthinker.bound_seconds(ops, nbytes, peak)
        assert bound == ops / 197e12 > nbytes / 819e9     # compute-bound
        assert bwd(1, 28, S, 128, 4096, 4 / 28) == (2 * ops, 2 * nbytes)
    assert bound == pytest.approx(9.77e-3, rel=1e-3)      # a full forward


def _ctx(model, name, opcount="opcount_smallthinker"):
    return {"name": name, "model": model, "opcount": opcount,
            "device_kind": "TPU v5 lite",
            "traffic": _json("benchmarks", "traffic", TRAFFIC + ".json")}


@pytest.mark.parametrize("name, per_call", [
    ("full_flash_fwd_roofline", 1), ("full_flash_bwd_roofline", 2),
    ("swa_flash_fwd_roofline", 1), ("swa_flash_bwd_roofline", 2)])
def test_flash_roofline_reader_on_synthetic_queries(model, name, per_call):
    """Both kinds' events at [1, 28, 16384, 128], each against its own
    rule's kept scores, through the reader the accepted window metrics
    have."""
    spec = _json("benchmarks", "metrics", name + ".json")
    ctx = _ctx(model, name)
    ops, _ = getattr(opcount_smallthinker, spec["opcount"])(
        1, 28, S, 128, 4096, 4 / 28)
    calls = 4
    q = {"total_s": calls * 2 * ops / 197e12, "count": calls * per_call,
         "dims": [1, 28, S, 128]}
    got = window_readers.flash_roofline(
        spec, {"trace": {"queries": {name: q}}}, ctx)
    assert got == pytest.approx(50.0, rel=1e-6)
    gmm = {"total_s": 1.0, "count": 3, "dims": [98304, 768]}
    for readings in ({"trace": {"queries": {name: gmm}}},
                     {"trace": {"queries": {name: None}}},
                     {"trace": {"queries": {}}}, {"trace": None}, {}):
        assert window_readers.flash_roofline(spec, readings, ctx) is None
    # a program with no window (the parent's other cells): nothing
    llama = {"d_model": 4096, "n_heads": 32, "n_kv_heads": 8, "d_head": 128}
    assert window_readers.flash_roofline(
        spec, {"trace": {"queries": {name: q}}},
        _ctx(llama, name, "opcount")) is None


def test_queries_tell_the_window_calls_from_the_full_ones():
    f_fwd, f_bwd, full, held = (re.compile(_json(
        "benchmarks", "metrics", n + ".json")["trace_query"]["op"])
        for n in NEW[:4])
    s_fwd, s_bwd, swa = (re.compile(_json(
        "benchmarks", "metrics", n + ".json")["trace_query"]["op"])
        for n in LENGTHENED[:3])
    tail = (' custom-call(%a, %b), custom_call_target="tpu_custom_call", '
            'backend_config={}')
    four = "bf16[1,28,16384,128]{3,2,1,0:T(8,128)(2,1)}"
    lse = "f32[1,28,16384,1]{3,2,1,0}"
    w_fwd = f"%jvp_swa.attend_.1 = ({four}, {lse})" + tail
    w_dq = f"%swa.attend.34 = {four}" + tail
    w_dkv = f"%swa.attend.35 = ({four}, {four})" + tail
    c_fwd = f"%jvp__.1 = ({four}, {lse})" + tail
    c_dq = f"%checkpoint.9 = {four}" + tail
    c_dkv = f"%checkpoint.8 = ({four}, {four})" + tail
    gmm = "%gmm.3 = bf16[49152,768]{1,0:T(8,128)(2,1)}" + tail
    tgmm = "%tgmm.1 = bf16[16,2560,768]{2,1,0:T(8,128)(2,1)}" + tail
    rows = "%row_tile.118 = bf16[49152,2560]{1,0:T(8,128)(2,1)}" + tail
    fusion = f"%fusion.7 = {four} fusion(%p), kind=kLoop"
    windows, fulls = (w_fwd, w_dq, w_dkv), (c_fwd, c_dq, c_dkv)
    others = (gmm, tgmm, rows, fusion)
    for fwd, bwd, both, mine, theirs in (
            (f_fwd, f_bwd, full, fulls, windows),
            (s_fwd, s_bwd, swa, windows, fulls)):
        assert fwd.search(mine[0])
        assert not any(fwd.search(x) for x in mine[1:] + theirs + others)
        assert bwd.search(mine[1]) and bwd.search(mine[2])
        assert not any(bwd.search(x) for x in mine[:1] + theirs + others)
        assert all(both.search(x) for x in mine)
        assert not any(both.search(x) for x in theirs + others)
    cond = "%conditional.4 = bf16[16384,2560]{1,0} conditional(%i, %a, %b)"
    logits = "%fusion.9 = f32[16384,64]{1,0} fusion(%p), kind=kOutput"
    topk = "%sort.2 = (f32[16384,6]{1,0}, s32[16384,6]{1,0}) sort(%a, %b)"
    pairs = "%sort.5 = (s32[98304]{0}, s32[98304]{0}) sort(%a, %b)"
    assert all(held.search(x) for x in (cond, logits, topk, pairs))
    assert not any(held.search(x) for x in windows + fulls + (gmm, fusion))
    assert moe_readers.op_time_share(
        {}, {"trace": {"window_s": 2.0, "queries": {"x": {"total_s": 0.5}}}},
        {"name": "x"}) == 25.0


def test_vmem_stated_share_reads_the_two_counters():
    spec = _json("benchmarks", "metrics", "flash_vmem_stated_share.json")
    read = lambda counters: counter_readers.ratio(  # noqa: E731
        spec, {"counters": counters}, {})
    assert read({"flash.kernels": 12, "flash.kernels_vmem_stated": 12}) == 100
    assert read({"flash.kernels": 9, "flash.kernels_vmem_stated": 0}) == 0
    # a program from before PR 50: nothing, and no raise
    assert read({"flash.steps_masked": 9}) is None


def test_benchmark_json_lists_the_cell_where_its_readers_read():
    bench = _json("BENCHMARK.json")
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert bench["workloads"][-1] == cell and bench["configs"][-1] == entry
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["num_hidden_layers",
                                "moe_num_primary_experts", "vocab_size"]
    traffic = _json("benchmarks", "traffic", TRAFFIC + ".json")
    assert (traffic["per_chip_batch"], traffic["seq"],
            traffic["batches_in_cycle"]) == (1, S, 8)
    listed = {m["name"]: m.get("workloads", [])
              for m in bench["per_layer"] + bench["end_to_end"]}
    for name in ("train_tokens_per_s_per_chip", "trainer_start_s",
                 "train_step_p50_ms", "train_stall_share",
                 "train_compiles_in_window", "train_mfu",
                 "device_idle_share.train", "peak_hbm_bytes.train",
                 "flash_unmasked_step_share", "flash_kv_fetch_share",
                 "cluster_init_s", "gang_place_s", "gang_backend_init_s",
                 "gang_mesh_s", "gang_open_chip_s", "gang_session_launch_s",
                 "trainer_start_covered_share", *LENGTHENED):
        assert listed[name][-1] == CELL, name
    # their readers count a causal half at one head count, or other shapes
    for name in ("flash_fwd_roofline", "flash_bwd_roofline",
                 "flash_band_step_share", "mla_flash_fwd_roofline",
                 "bd_flash_fwd_roofline", "moe_gmm_roofline",
                 "moe_held_time_share", "laguna_moe_held_time_share",
                 "laguna_full_attention_time_share",
                 "tp_collective_time_share"):
        assert CELL not in listed[name], name
    mine = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in mine] == list(NEW)
    for m in mine:
        assert m["moves"] == "train_tokens_per_s_per_chip"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "metrics", m["name"] + ".json"))
    assert [m["workloads"] for m in mine[:4]] == [[CELL]] * 4
    assert mine[4]["workloads"] == [w["name"] for w in bench["workloads"]]
    assert len(bench["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_reference_is_independent_of_the_program():
    for name in ("reference_smallthinker.py", "opcount_smallthinker.py"):
        with open(os.path.join(ROOT, "benchmarks", name)) as f:
            source = f.read()
        imports = re.findall(r"^\s*(?:from|import)\s+([\w.]+)", source, re.M)
        assert not [m for m in imports if m.startswith("ray_tpu")], imports


def test_rehearsal_runs_the_cells_files():
    """Exit 3, every listed metric's file loads and its reader runs. NOT
    asserted: `correct` (test_laguna_arithmetic.py on why: at the
    rehearsal's width in bf16 a few tokens in a hundred choose another
    expert than the float32 reference's; in float32 the program is the
    reference, tests/test_window_moe_reference.py)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "3000000123", "--seconds", "3",
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
                  "flash_unmasked_step_share", "flash_kv_fetch_share",
                  *LENGTHENED, *NEW}
    for name, was in readable.items():
        assert was is True or name in needs_chip, (name, was)
