"""Checks of the EvaByte cell's files (`train-evabyte-1chip`: EVA attention
over [2,048 chunk summaries ; 32,768 bytes] in one flash call a layer, eight
next-byte heads over a 320-row vocabulary, a float32 residual stream), its
arithmetic, readers and queries, and the rehearsal of its files; on the CPU,
no chip:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/checks -q
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import counter_readers, eva_readers, moe_readers, peaks  # noqa: E402
from benchmarks import opcount_evabyte as opcount  # noqa: E402

CELL = "train-evabyte-1chip"
CONFIG = "evabyte-train-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
S = 32_768
KEPT = (33_570_816, 31_457_280)


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


def test_configuration_keeps_every_published_key(config):
    """Every key of the catalog's `config` under the same name and value but
    the depth; every width as published; what the row lacks is `assumed`,
    the scale on the pooling logits and the heads' weights among them; the
    floors of a `model_config` PR."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f) if r["name"] == "EvaByte"]
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == config["reduced"] == ["num_hidden_layers"]
    assert config["deployment"]["published"] == {"num_hidden_layers": 32}
    assert config["deployment"]["chips_sharing_a_layer"] == 1
    for key, value in (("hidden_size", 4096), ("intermediate_size", 11008),
                       ("num_attention_heads", 32),
                       ("num_key_value_heads", 32), ("window_size", 2048),
                       ("chunk_size", 16), ("num_pred_heads", 8),
                       ("vocab_size", 320), ("rope_theta", 100000),
                       ("max_seq_length", 32768), ("attention_class", "eva"),
                       ("norm_add_unit_offset", True),
                       ("fp32_skip_add", True), ("fp32_logits", True),
                       ("fp32_ln", False), ("mixedp_attn", True),
                       ("init_std", 0.01275), ("rms_norm_eps", 1e-5),
                       ("tie_word_embeddings", False)):
        assert config[key] == row["config"][key] == value, key
    assert config["num_hidden_layers"] == 4 == len(config["layers_held"])
    for key in ("layer", "pooling_scale", "head_weights", "head_dim",
                "precision", "weights", "partial_window"):
        assert key in config["assumed"]
    assert config["head_dim"] * config["num_attention_heads"] \
        == config["hidden_size"]
    assert len(json.dumps(config)) < 64 * 1024


def test_parameters_against_the_issues_table_and_the_program(model):
    """ISSUE 57's table, to the parameter."""
    d, ff = 4096, 11008
    attn, mlp = 4 * d * d, 3 * d * ff
    assert (attn, mlp) == (67_108_864, 135_266_304)
    assert opcount.layer_matmul_params(model) == attn + mlp
    assert opcount.layer_params(model) == attn + mlp + 8_192 + 8_192 \
        == 202_391_552
    assert opcount.head_params(model) == d * 2_560 == 10_485_760
    assert opcount.num_params(model) \
        == 4 * 202_391_552 + 1_310_720 + 10_485_760 + d == 821_366_784
    assert opcount.num_params(dict(model, n_layers=32)) == 6_488_330_240
    from ray_tpu.models import evabyte

    cfg = evabyte.EvaByteConfig(**model)
    assert cfg.num_params() == 821_366_784
    assert (cfg.window, cfg.chunk, cfg.pred_heads) == (2048, 16, 8)
    assert cfg.remat_policy == "residuals" and cfg.loss_chunk_size == 1024
    # bf16 weights and two bf16 AdamW moments: 6 B a parameter of arguments
    assert 6 * 821_366_784 / 2**30 == pytest.approx(4.59, abs=0.005)


def test_kept_scores_and_tiles_against_a_hand_count():
    """A window's own causal bytes, 16 windows; window w's 2,048 queries see
    128 w summaries; in 512 x 512 tiles 160 + 144."""
    local = 16 * (2048 * 2049 // 2)
    summary = sum(2048 * 128 * w for w in range(16))
    assert (local, summary) == KEPT == opcount.kept_scores(S, 2048, 16)
    assert local + summary == 65_028_096
    assert S * (S + 1) // 2 == 536_887_296
    assert round(536_887_296 / 65_028_096, 2) == 8.26
    assert round(100 * summary / (local + summary), 1) == 48.4
    own_tiles = 16 * (1 + 2 + 3 + 4)
    summary_tiles = sum(4 * -(-128 * w // 512) for w in range(16))
    assert (own_tiles, summary_tiles) == (160, 144)
    assert round(304 * 512 * 512 / 65_028_096, 2) == 1.23
    # the fallback traffic, and a last window that is partial
    assert opcount.kept_scores(16_384, 2048, 16) == (16_785_408, 7_340_032)
    assert round(100 * 7_340_032 / 24_125_440, 1) == 30.4
    assert opcount.kept_scores(80, 32, 4) == (2 * 528 + 136, 32 * 8 + 16 * 16)
    from ray_tpu.ops.flash_attention import EvaWindows, block_schedule

    for s, w, c in ((S, 2048, 16), (16_384, 2048, 16), (80, 32, 4)):
        assert EvaWindows(s, w, c).kept(s, s + s // c) \
            == opcount.kept_scores(s, w, c)
    plan = block_schedule(S, S + 2048, 512, 512, EvaWindows(S, 2048, 16))
    assert len(plan["fwd"].tiles) == len(plan["dkv"].tiles) == 304


def test_operation_counts_against_a_hand_count_of_one_layer(model):
    d, ff, hd = 4096, 11008, 32 * 128
    weights = 2 * (4 * d * hd + 3 * d * ff)                  # a token
    scores = 2 * 2 * hd * sum(KEPT) / S
    pooling = 6 * hd
    layer = weights + scores + pooling
    head = 2 * d * 8 * 320
    parts = opcount.forward_flops_by_part(model, S)
    assert sum(parts.values()) == pytest.approx(4 * layer + head, rel=1e-12)
    assert opcount.train_flops_per_token(model, S) \
        == pytest.approx(3 * (4 * layer + head), rel=1e-12)
    # ISSUE 57's reckoning: a layer's forward 13.26 TFLOP of weights'
    # matmuls + 1.07 of kept scores; a four-layer step ~174 TFLOP
    assert round(weights * S / 1e12, 2) == 13.26
    assert round(scores * S / 1e12, 2) == 1.07
    step = opcount.train_flops_per_token(model, S) * S
    assert round(step / 1e12, 1) == 174.0
    total = sum(parts.values())
    share = lambda *names: round(  # noqa: E731
        100 * sum(parts[n] for n in names) / total, 1)
    # the cell's `why`: attention is what the architecture makes cheap
    assert share("scores_local", "scores_summary") == 7.3
    assert share("mlp") == 61.1 and share("projections") == 30.3
    assert share("head") == 1.2 and share("pooling") == 0.0


def test_kernel_bounds_at_the_cells_shape():
    peak = peaks.peaks("TPU v5 lite")
    ops, nbytes = opcount.eva_flash_fwd(1, 32, S, 128, 2048, 16)
    assert ops == 4 * 32 * 128 * 65_028_096
    assert nbytes == 2 * 32 * 128 * (2 * S + 2 * 34_816)
    assert opcount.bound_seconds(ops, nbytes, peak) == ops / 197e12 \
        == pytest.approx(5.408e-3, rel=1e-3)
    ops_b, nbytes_b = opcount.eva_flash_bwd(1, 32, S, 128, 2048, 16)
    assert (ops_b, nbytes_b) == (2 * ops, 2 * nbytes)
    assert opcount.bound_seconds(ops_b, nbytes_b, peak) \
        == pytest.approx(10.816e-3, rel=1e-3)
    # the pooling: K and V read once, a sixteenth of each written
    p_ops, p_bytes = opcount.eva_summarise(1, 32, S, 128, 16)
    assert p_bytes == 2 * 32 * 128 * (2 * S + 2 * 2048)
    assert opcount.bound_seconds(p_ops, p_bytes, peak) == p_bytes / 819e9 \
        == pytest.approx(0.696e-3, rel=2e-3)


def _ctx(model, name):
    return {"name": name, "model": model, "opcount": "opcount_evabyte",
            "device_kind": "TPU v5 lite",
            "traffic": _json("benchmarks", "traffic", "pretrain-32k-b1.json")}


def test_the_eva_reader_reads_both_rooflines(model):
    """The forward's o and dq are [1, 32, S, 128], dk [1, 32, S + S / 16,
    128]: whichever event comes first, S is the traffic's."""
    peak = peaks.peaks("TPU v5 lite")
    for name, fn, events in (
            ("eva_flash_fwd_roofline", opcount.eva_flash_fwd, 1),
            ("eva_flash_bwd_roofline", opcount.eva_flash_bwd, 2)):
        spec = _json("benchmarks", "metrics", name + ".json")
        bound = opcount.bound_seconds(*fn(1, 32, S, 128, 2048, 16), peak)
        for rows in (S, 34_816):
            q = {"total_s": 4 * 2 * bound, "count": 4 * events,
                 "dims": [1, 32, rows, 128]}
            assert eva_readers.flash_roofline(
                spec, {"trace": {"queries": {name: q}}}, _ctx(model, name)) \
                == pytest.approx(50.0, rel=1e-6)
        odd = {"total_s": 1.0, "count": 4, "dims": [1, 32, 4096, 128]}
        assert eva_readers.flash_roofline(
            spec, {"trace": {"queries": {name: odd}}},
            _ctx(model, name)) is None
        # a program without the call, or a model without the fields
        assert eva_readers.flash_roofline(
            spec, {"trace": {"queries": {}}}, _ctx(model, name)) is None
        assert eva_readers.flash_roofline(
            spec, {"trace": None}, _ctx(model, name)) is None
        q = {"total_s": 1.0, "count": 4, "dims": [1, 32, S, 128]}
        assert eva_readers.flash_roofline(
            spec, {"trace": {"queries": {name: q}}},
            _ctx({"d_model": 4096}, name)) is None


def test_the_time_shares_read_their_queries(model):
    for name in ("eva_attention_time_share", "eva_summarise_time_share"):
        spec = _json("benchmarks", "metrics", name + ".json")
        assert spec["reader"] == "moe_readers.op_time_share"
        readings = {"trace": {"window_s": 4.0, "queries": {
            name: {"total_s": 0.5, "count": 12, "dims": [1, 32, S, 128]}}}}
        assert moe_readers.op_time_share(spec, readings, _ctx(model, name)) \
            == pytest.approx(12.5)
        assert moe_readers.op_time_share(
            spec, {"trace": {"window_s": 4.0, "queries": {}}},
            _ctx(model, name)) is None


def test_the_queries_take_the_events_they_are_for():
    """The three kernels as the compiled step names them, the pooling's
    fusions by their shapes, and nothing of another layer's."""
    import re

    query = lambda name: re.compile(_json(  # noqa: E731
        "benchmarks", "metrics", name + ".json")["trace_query"]["op"])
    tail = ' custom-call(%a), custom_call_target="tpu_custom_call"'
    fwd = ("%eva.attend.32 = (bf16[1,32,32768,128]{3,2,1,0:T(8,128)(2,1)}, "
           "f32[1,32,32768,1]{3,2,1,0:T(8,128)})" + tail)
    dq = "%eva.attend.30 = bf16[1,32,32768,128]{3,2,1,0:T(8,128)(2,1)}" + tail
    dkv = ("%eva.attend.31 = (bf16[1,32,34816,128]{3,2,1,0:T(8,128)(2,1)}, "
           "bf16[1,32,34816,128]{3,2,1,0:T(8,128)(2,1)})" + tail)
    causal = ("%jvp__.1 = (bf16[1,32,32768,128]{3,2,1,0}, "
              "f32[1,32,32768,1]{3,2,1,0})" + tail)
    took = lambda name, op: bool(query(name).search(op))  # noqa: E731
    calls = (fwd, dq, dkv, causal)
    assert [took("eva_flash_fwd_roofline", x) for x in calls] \
        == [True, False, False, False]
    assert [took("eva_flash_bwd_roofline", x) for x in calls] \
        == [False, True, True, False]
    assert [took("eva_attention_time_share", x) for x in calls] \
        == [True, True, True, False]
    pooled = ["%fusion.414 = f32[2048,16,32]{1,0,2:T(8,128)S(1)} fusion(%p)",
              "%fusion.383 = (f32[2048,32]{0,1:T(8,128)S(1)}, "
              "f32[2048,16,32]{1,0,2}) fusion(%p)",
              "%fusion.417 = bf16[1,2048,32,128]{3,1,2,0} fusion(%p)",
              "%multiply_reduce_fusion.29 = bf16[2048,32,128]{2,0,1} "
              "fusion(%p)",
              "%add_multiply_fusion.4 = f32[32768,32]{0,1} fusion(%p)"]
    other = ["%fusion.9 = bf16[1,32768,32,128]{3,2,1,0} fusion(%p)",
             "%fusion.2 = f32[1,32768,4096]{2,1,0} fusion(%p)",
             "%fusion.3 = bf16[8192,11008]{1,0} fusion(%p)",
             "%fusion.4 = f32[1,1024,2560]{2,1,0} fusion(%p)",
             "%fusion.5 = bf16[2048,4096]{1,0} fusion(%p)", fwd, dq, dkv]
    assert all(took("eva_summarise_time_share", x) for x in pooled)
    assert not any(took("eva_summarise_time_share", x) for x in other)


def test_the_counter_shares_read_the_counters():
    read = lambda name, counters: counter_readers.ratio(  # noqa: E731
        _json("benchmarks", "metrics", name + ".json"),
        {"counters": counters}, {})
    # per lowering: the step's and the reference check's forward add up
    counters = {"eva.calls": 2, "eva.scores_local": 2 * KEPT[0],
                "eva.scores_summary": 2 * KEPT[1]}
    assert read("eva_summary_score_share", counters) \
        == pytest.approx(48.374, abs=1e-3)
    # the parent's program counts none of them: nothing read, nothing raised
    assert read("eva_summary_score_share", {"flash.kernels": 3}) is None
    assert read("eva_summary_score_share", {}) is None


def test_the_cell_is_listed_where_its_metrics_are_read():
    bench = _json("BENCHMARK.json")
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain-32k-b1", 1)
    assert len(cell["why"]) <= 200
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == ["num_hidden_layers"] \
        and len(entry["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    new = {"eva_flash_fwd_roofline", "eva_flash_bwd_roofline",
           "eva_attention_time_share", "eva_summarise_time_share",
           "eva_summary_score_share"}
    assert new <= listed and len(listed) == 32 + len(new)
    for name in ("train_mfu", "flash_vmem_stated_share",
                 "flash_unmasked_step_share", "flash_kv_fetch_share",
                 "peak_hbm_bytes.train", "ce_fused_chunk_share",
                 "embed_grad_sorted_row_share"):
        assert name in listed, name
    # the causal calls' rooflines count a causal half: not this call's
    for name in ("flash_fwd_roofline", "flash_bwd_roofline",
                 "swa_flash_fwd_roofline", "ssd_time_share"):
        assert name not in listed, name
    for m in bench["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_tokens_per_s_per_chip"
    (rate,) = [m for m in bench["end_to_end"]
               if m["name"] == "train_tokens_per_s_per_chip"]
    assert rate["workloads"][-1] == CELL and len(rate["workloads"]) == 11


def test_the_cells_files_rehearse_on_the_cpu():
    """`run.py --rehearse` of the cell: its configuration, traffic,
    reference, opcount and metric files at tiny sizes through the harness's
    own path (exit 3: not a measurement), the program's loss on the
    reference's, falling in warm-up, the counters' metrics readable."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=900)
    assert proc.returncode == 3, proc.stderr[-2000:]
    (line,) = [ln for ln in proc.stdout.splitlines()
               if ln.startswith('{"rehearsal"')]
    got = json.loads(line)["rehearsal"]
    assert got["correct"] and got["failed"] == 0
    assert got["checks"]["loss_rel_err"] < 3e-4
    warm = got["checks"]["warmup_losses"]
    assert warm[-1] < warm[0] < 6.5
    readable = got["metric_was_readable"]
    for name in ("eva_summary_score_share", "ce_fused_chunk_share",
                 "embed_grad_sorted_row_share"):
        assert readable[name] is True, name
