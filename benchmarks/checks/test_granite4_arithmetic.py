"""Checks of the Granite cell's files (`train-granite4-1chip`: Mamba-2 at ONE
group of 64 heads, a 64-wide-head attention layer without RoPE, a dense MLP a
layer, a tied head), its arithmetic, readers and queries; on the CPU, no
chip:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/checks -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import counter_readers, peaks, readers, ssd_readers  # noqa: E402
from benchmarks import opcount_granite4 as opcount  # noqa: E402
from benchmarks import opcount_nemotron3  # noqa: E402

CELL = "train-granite4-1chip"
CONFIG = "granite-4.0-h-micro-train-1chip"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
S = 32_768


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
    """Every key of the catalog's `config` under the same name and value,
    lists whole, but the depth; the four multipliers, the tied head, `nope`
    and the 40 `layer_types` as published; what the row lacks is `assumed`;
    the floors of a `model_config` PR."""
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog on this machine")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "granite-4.0-h-micro"]
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == config["reduced"] == ["num_hidden_layers"]
    assert config["deployment"]["published"] == {"num_hidden_layers": 40}
    for key, value in (("embedding_multiplier", 12),
                       ("residual_multiplier", 0.22),
                       ("attention_multiplier", 0.015625),
                       ("logits_scaling", 8), ("tie_word_embeddings", True),
                       ("position_embedding_type", "nope"),
                       ("mamba_chunk_size", 256), ("mamba_n_groups", 1)):
        assert config[key] == row["config"][key] == value
    kinds, held = config["layer_types"], config["layers_held"]
    assert len(kinds) == 40 and held == list(range(10))
    assert len(held) == config["num_hidden_layers"] >= 4
    assert [kinds[i][0] for i in held] == list("mmmmmammmm")
    assert kinds == kinds[:10] * 4                     # one whole period
    for key in ("time_step", "mamba2", "attention", "weights", "tied_head",
                "multipliers"):
        assert key in config["assumed"]
    assert config["head_dim"] * config["num_attention_heads"] \
        == config["hidden_size"]
    # no multiplier is folded: each is a field of the program's config
    for name in ("embedding_multiplier", "residual_multiplier",
                 "attention_multiplier", "logits_scaling"):
        assert config["program"]["fields_from"][name] == name


def test_parameters_against_the_issues_table_and_the_program(model):
    """ISSUE 55's table, to the parameter."""
    d, ff = 2048, 8192
    mixer = d * 8_512 + 5 * 4_352 + 3 * 64 + 4_096 + 4_096 * d
    assert mixer == opcount.mixer_params(model, "mamba") == 25_847_232
    attn = 2 * d * 64 * (32 + 8)
    assert attn == opcount.mixer_params(model, "attention") == 10_485_760
    mlp = 3 * d * ff
    assert mlp == 50_331_648
    assert opcount.layer_params(model, "mamba") == mixer + mlp + 4_096 \
        == 76_182_976
    assert opcount.layer_params(model, "attention") == attn + mlp + 4_096 \
        == 60_821_504
    embedding = 100_352 * d
    assert embedding == 205_520_896
    assert opcount.num_params(model) == 9 * 76_182_976 + 60_821_504 \
        + embedding + d == 951_991_232
    whole = dict(model, layers=None)
    assert opcount.num_params(whole) == 3_191_396_096
    from ray_tpu.models import granite_hybrid

    cfg = granite_hybrid.GraniteHybridConfig(**model)
    assert cfg.num_params() == 951_991_232
    assert cfg.plan() == ([], [0], [("periods", 1)])
    assert cfg.runs() == [("mamba", 5), ("attention", 1), ("mamba", 4)]
    assert cfg.rope_theta == 0 and cfg.chunk_size == opcount.CHUNK
    assert 6 * 951_991_232 / 2**30 == pytest.approx(5.32, abs=0.005)


def test_operation_counts_against_hand_sums(model):
    c = opcount.CHUNK
    assert c == 256
    chunk = 2 * c * c * 128 + 64 * (2 * c * c * 64 + 4 * c * 128 * 64)
    assert opcount_nemotron3.ssd_chunk_ops(64, 64, 128, c) == chunk
    assert opcount.scan_token_ops(model) == chunk / c
    d = 2048
    mamba = 2 * (d * 8_512 + 4_096 * d + 4 * 4_352) + chunk / c
    kept = S * (S + 1) // 2
    assert opcount.kept_scores(S) == kept
    attn = 2 * (2 * d * 64 * 40) + 2 * 2 * 32 * 64 * kept / S
    mlp = 2 * 3 * d * 8_192
    head = 2 * d * 100_352
    assert opcount.mixer_token_ops(model, "mamba", S) == mamba
    assert opcount.mixer_token_ops(model, "attention", S) \
        == pytest.approx(attn, rel=1e-12)
    forward = head + 9 * mamba + attn + 10 * mlp
    assert opcount.forward_flops_per_token(model, S) \
        == pytest.approx(forward, rel=1e-12)
    got = opcount.train_flops_per_token(model, S)
    assert got == pytest.approx(3 * forward, rel=1e-12)
    # ISSUE 55's reckoning: ~6.2 GFLOP a token trained, 5.71 of them the
    # weights' matmuls, 0.40 the kept scores, ~0.12 the scan
    assert round(got / 1e9, 2) == 6.23
    assert round(6 * 951_991_232 / 1e9, 2) == 5.71
    assert round(3 * 4 * 32 * 64 * kept / S / 1e9, 2) == 0.40
    assert round(3 * 9 * chunk / c / 1e9, 3) == 0.115
    # the cell's `why`: the head's share at 10 layers and at 40, the MLP's,
    # the mixers' under half
    assert round(100 * head / forward, 1) == 19.8
    full = opcount.forward_flops_per_token(dict(model, layers=None), S)
    assert round(100 * head / full, 1) == 5.8
    assert round(100 * 10 * mlp / forward, 1) == 48.5
    assert 100 * (9 * mamba + attn) / forward < 50


def test_kernel_bounds_at_the_cells_shape():
    peak = peaks.peaks("TPU v5 lite")
    ops, nbytes = opcount.ssd_fwd(1, 64, S, 64, 1, 128)
    assert ops == 128 * opcount_nemotron3.ssd_chunk_ops(64, 64, 128, 256)
    assert nbytes == (2 * S * (2 * 4096 + 2 * 128) + 2 * 4 * S * 64
                      + 4 * 64 * 64 * 128)
    fwd = opcount.bound_seconds(ops, nbytes, peak)
    # at a chunk of 256 the chunked form's matmuls are just over its bytes
    assert fwd == ops / 197e12 == pytest.approx(0.709e-3, rel=5e-3)
    assert nbytes / 819e9 == pytest.approx(0.699e-3, rel=5e-3)
    ops_b, nbytes_b = opcount.ssd_bwd(1, 64, S, 64, 1, 128)
    assert ops_b == 2 * ops
    assert opcount.bound_seconds(ops_b, nbytes_b, peak) == ops_b / 197e12 \
        == pytest.approx(1.417e-3, rel=5e-3)
    assert nbytes_b / 819e9 == pytest.approx(1.065e-3, rel=5e-3)
    # the flash call: the kept scores at a 64-wide contraction
    f_ops, f_bytes = opcount.flash_fwd(1, 32, S, 64, 0.25)
    assert f_ops == 4 * 32 * 64 * (S * (S + 1) // 2)
    assert f_bytes == 2 * S * 64 * (2 * 32 + 2 * 8)
    assert opcount.bound_seconds(f_ops, f_bytes, peak) == f_ops / 197e12 \
        == pytest.approx(22.33e-3, rel=5e-3)
    b_ops, b_bytes = opcount.flash_bwd(1, 32, S, 64, 0.25)
    assert b_ops == 2 * f_ops and b_bytes == 2 * f_bytes


def _ctx(model, name):
    return {"name": name, "model": model, "opcount": "opcount_granite4",
            "device_kind": "TPU v5 lite",
            "traffic": _json("benchmarks", "traffic", "pretrain-32k-b1.json")}


def test_the_traffic_is_pretrain_16k_at_twice_the_length():
    ours = _json("benchmarks", "traffic", "pretrain-32k-b1.json")
    theirs = _json("benchmarks", "traffic", "pretrain-16k-b1.json")
    differ = {k for k in theirs if ours[k] != theirs[k]}
    assert differ == {"seq", "note"} and set(ours) == set(theirs)
    assert (ours["seq"], theirs["seq"]) == (S, 16_384)


def test_the_accepted_ssd_reader_reads_the_wide_group(model):
    """`ssd_readers.kernel_roofline` unchanged: the forward's y and the
    backward's dx are [1, S, 4096]; the states keep the CALL's dims, [1 x 1
    group, 128 chunks x 128, 4096], though the kernels walk them in head
    blocks."""
    peak = peaks.peaks("TPU v5 lite")
    name = "ssd_fwd_roofline"
    spec = _json("benchmarks", "metrics", name + ".json")
    bound = opcount.bound_seconds(*opcount.ssd_fwd(1, 64, S, 64, 1, 128), peak)
    q = {"total_s": 9 * 4 * bound, "count": 9, "dims": [1, S, 4096]}
    assert ssd_readers.kernel_roofline(
        spec, {"trace": {"queries": {name: q}}}, _ctx(model, name)) \
        == pytest.approx(25.0, rel=1e-6)
    name_b = "ssd_bwd_roofline"
    spec_b = _json("benchmarks", "metrics", name_b + ".json")
    bound_b = opcount.bound_seconds(
        *opcount.ssd_bwd(1, 64, S, 64, 1, 128), peak)
    for dims in ([1, 128 * 128, 4096], [1, S, 4096]):
        q_b = {"total_s": 9 * 5 * bound_b, "count": 18, "dims": dims}
        assert ssd_readers.kernel_roofline(
            spec_b, {"trace": {"queries": {name_b: q_b}}},
            _ctx(model, name_b)) == pytest.approx(20.0, rel=1e-6)
    # a head block's own dims would not be the call's: nothing is read
    odd = {"total_s": 1.0, "count": 18, "dims": [4, 128 * 128, 1024]}
    assert ssd_readers.kernel_roofline(
        spec_b, {"trace": {"queries": {name_b: odd}}},
        _ctx(model, name_b)) is None


def test_the_accepted_flash_reader_reads_the_64_wide_call(model):
    peak = peaks.peaks("TPU v5 lite")
    for name, fn, events in (("flash_fwd_roofline", opcount.flash_fwd, 1),
                             ("flash_bwd_roofline", opcount.flash_bwd, 2)):
        spec = _json("benchmarks", "metrics", name + ".json")
        bound = opcount.bound_seconds(*fn(1, 32, S, 64, 0.25), peak)
        q = {"total_s": 3 * 2 * bound, "count": 3 * events,
             "dims": [1, 32, S, 64]}
        assert readers.kernel_roofline(
            spec, {"trace": {"queries": {name: q}}}, _ctx(model, name)) \
            == pytest.approx(50.0, rel=1e-6)


def test_the_wide_group_share_reads_the_counters():
    spec = _json("benchmarks", "metrics", "ssd_wide_group_share.json")
    read = lambda counters: counter_readers.ratio(  # noqa: E731
        spec, {"counters": counters}, {})
    assert read({"ssd.kernels": 6, "ssd.kernels_wide_group": 6}) == 100.0
    assert read({"ssd.kernels": 3, "ssd.kernels_wide_group": 0}) == 0.0
    # the parent's program counts neither: nothing is read, nothing raises
    assert read({"ssd.calls": 2}) is None
    assert read({}) is None


def test_the_cell_is_listed_where_its_metrics_are_read():
    bench = _json("BENCHMARK.json")
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "pretrain-32k-b1", 1)
    assert len(cell["why"]) <= 200
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [CELL])}
    for name in ("train_mfu", "ssd_fwd_roofline", "ssd_bwd_roofline",
                 "ssd_time_share", "flash_fwd_roofline", "flash_bwd_roofline",
                 "ssd_wide_group_share", "flash_vmem_stated_share",
                 "flash_unmasked_step_share", "flash_kv_fetch_share",
                 "peak_hbm_bytes.train", "ce_fused_chunk_share"):
        assert name in listed, name
    # a loop plan has no triangle step; no experts, no window, no latent
    for name in ("flash_triangle_step_share", "moe_gmm_partial_tile_share",
                 "swa_flash_fwd_roofline", "mla_flash_fwd_roofline"):
        assert name not in listed, name
    (wide,) = [m for m in bench["per_layer"]
               if m["name"] == "ssd_wide_group_share"]
    # by name, not by position: a later PR appends after these
    assert wide["workloads"][:2] == ["train-nemotron3-1chip", CELL]
