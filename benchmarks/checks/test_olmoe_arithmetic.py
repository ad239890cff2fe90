"""Checks of the routed-experts cell's arithmetic and readers; seconds on
the CPU, no chip:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/checks -q
"""

import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import moe_readers, opcount, opcount_olmoe, peaks  # noqa: E402


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def model():
    config = _json("benchmarks", "configs",
                   "olmoe-1b-7b-0125-train-1chip.json")
    program = config["program"]
    fields = {f: config[k] for f, k in program["fields_from"].items()}
    return dict(fields, **program["fields"])


def test_active_parameters_against_a_hand_sum(model):
    # one layer: q, k, v, o 4 x 2048 x 2048; router 2048 x 64; 8 of 64
    # experts x 3 matrices of 2048 x 1024
    layer = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert layer == 67_239_936
    assert opcount_olmoe.layer_active_matmul_params(model) == layer
    head = 2048 * 50304
    assert opcount_olmoe.active_matmul_params(model) == 3 * layer + head
    # 3 x (2 ops a weight + causal scores: 2 x 16 x 128 x 2048 a layer)
    want = 3 * (2 * (3 * layer + head) + 3 * 2 * 16 * 128 * 2048)
    got = opcount_olmoe.train_flops_per_token(model, 2048)
    assert got == want and round(got / 1e9, 3) == 1.904
    # the shares the configuration's `why_reduced` states (3 layers)
    shares = {"experts": 3 * 8 * 3 * 2048 * 1024 * 6 / got,
              "lm_head": head * 6 / got,
              "projections": 3 * 4 * 2048 * 2048 * 6 / got,
              "scores": 3 * 3 * 2 * 16 * 128 * 2048 / got}
    assert {k: round(100 * v, 1) for k, v in shares.items()} == {
        "experts": 47.6, "lm_head": 32.5, "projections": 15.9, "scores": 4.0}
    # a dense model of these fields counts the same attention and head
    dense = dict(model, d_ff=0)
    assert (opcount.matmul_params(dense)
            == opcount_olmoe.active_matmul_params(dict(
                model, experts_per_token=0, n_experts=0)))


def test_gmm_bound_at_the_cells_shape():
    ops, nbytes = opcount_olmoe.moe_gmm(65536, 2048, 1024, 64)
    assert ops == 2 * 65536 * 2048 * 1024
    assert nbytes == 2 * (65536 * 2048 + 64 * 2048 * 1024 + 65536 * 1024)
    bound = opcount_olmoe.bound_seconds(ops, nbytes,
                                        peaks.peaks("TPU v5 lite"))
    assert bound == pytest.approx(1.395e-3, rel=1e-3)   # compute-bound
    assert bound == ops / 197e12 > nbytes / 819e9
    # the down projection and both backward calls: same ops, same bytes
    assert opcount_olmoe.moe_gmm(65536, 1024, 2048, 64) == (ops, nbytes)


def _ctx(model, name):
    return {"name": name, "model": model, "opcount": "opcount_olmoe",
            "device_kind": "TPU v5 lite",
            "traffic": _json("benchmarks", "traffic", "pretrain-2k.json")}


def test_gmm_roofline_reader_on_synthetic_queries(model):
    ctx = _ctx(model, "moe_gmm_roofline")
    spec = _json("benchmarks", "metrics", "moe_gmm_roofline.json")
    # 4 steps x 3 layers x 12 calls at twice the bound each -> 50%
    calls = 4 * 3 * 12
    for dims in ([65536, 1024], [65536, 2048], [64, 2048, 1024]):
        q = {"total_s": calls * 2 * 1.395431e-3, "count": calls, "dims": dims}
        got = moe_readers.gmm_roofline(
            spec, {"trace": {"queries": {"moe_gmm_roofline": q}}}, ctx)
        assert got == pytest.approx(50.0, rel=1e-4)
    # some other kernel's event, no event, no trace: nothing is read
    other = {"total_s": 1.0, "count": 3, "dims": [4, 16, 2048, 128]}
    for readings in ({"trace": {"queries": {"moe_gmm_roofline": other}}},
                     {"trace": {"queries": {"moe_gmm_roofline": None}}},
                     {"trace": {"queries": {}}}, {"trace": None}, {}):
        assert moe_readers.gmm_roofline(spec, readings, ctx) is None


def test_gmm_query_matches_the_grouped_matmuls_and_no_flash_kernel():
    rx = re.compile(_json("benchmarks", "metrics",
                          "moe_gmm_roofline.json")["trace_query"]["op"])
    tail = (' custom-call(%a, %b), custom_call_target="tpu_custom_call", '
            'backend_config={}')
    gmm = "%gmm.3 = bf16[65536,1024]{1,0:T(8,128)(2,1)}" + tail
    tgmm = "%tgmm.1 = bf16[64,2048,1024]{2,1,0:T(8,128)(2,1)}" + tail
    flash_fwd = ("%f.1 = (bf16[4,16,2048,128]{3,2,1,0}, "
                 "f32[4,16,2048,1]{3,2,1,0})" + tail)
    flash_dq = "%dq.1 = bf16[4,16,2048,128]{3,2,1,0:T(8,128)(2,1)}" + tail
    flash_dkv = ("%dkv.1 = (bf16[4,16,2048,128]{3,2,1,0}, "
                 "bf16[4,16,2048,128]{3,2,1,0})" + tail)
    fusion = "%fusion.7 = bf16[65536,1024]{1,0} fusion(%p), kind=kLoop"
    assert rx.search(gmm) and rx.search(tgmm)
    assert not any(rx.search(x)
                   for x in (flash_fwd, flash_dq, flash_dkv, fusion))
    # why the cell is not listed under flash_bwd_roofline: that query
    # takes any Pallas call with one bf16 output (PERF.md §7)
    bwd = re.compile(_json("benchmarks", "metrics",
                           "flash_bwd_roofline.json")["trace_query"]["op"])
    assert bwd.search(gmm) and bwd.search(tgmm)
    cell = "train-olmoe-1chip"
    listed = {m["name"]: m.get("workloads", [])
              for m in _json("BENCHMARK.json")["per_layer"]}
    assert cell not in listed["flash_bwd_roofline"]
    assert cell in listed["flash_fwd_roofline"]
    assert cell in listed["moe_gmm_roofline"]


def test_dispatch_query_matches_the_moe_blocks_ops_only():
    rx = re.compile(_json("benchmarks", "metrics",
                          "moe_dispatch_time_share.json")["trace_query"]["op"])
    hit = [
        "%fusion.956 = bf16[65536,2048]{1,0:T(8,128)(2,1)} fusion(bf16[65536,"
        "2048]{1,0} %add_any.420, s32[65536]{0} %copy-done.49), kind=kCustom",
        "%sort.207 = (s32[65536]{0:T(1024)}, s32[65536]{0}) sort(s32[65536]",
        "%sort.204 = (f32[8192,64]{0,1}, s32[8192,64]{0,1}) sort(f32[8192,64]",
        "%fusion.959 = f32[524288]{0:T(1024)S(1)} fusion(f32[524288]{0}",
        "%add_any.420 = bf16[65536,2048]{1,0} add(bf16[65536,2048]{1,0} %a",
    ]
    miss = [
        "%gmm.45 = bf16[65536,1024]{1,0:T(8,128)(2,1)} custom-call(s32[] %g), "
        'custom_call_target="tpu_custom_call"',
        "%fusion.949 = bf16[4,2048,2048]{2,1,0} fusion(f32[4,2048]{1,0} %f)",
        "%fusion.876 = (bf16[4,1024]{1,0}, bf16[4,1024,50304]{2,1,0}) fusion(",
        "%fusion.635 = (bf16[3,64,2048,1024]{3,2,1,0}, bf16[]) fusion(",
        "%dynamic-slice_bitcast_fusion.41 = bf16[64,2048,1024]{2,1,0} fusion(",
    ]
    assert all(rx.search(x) for x in hit)
    assert not any(rx.search(x) for x in miss)


def test_op_time_share_reader():
    ctx = {"name": "x"}
    readings = {"trace": {"window_s": 2.0,
                          "queries": {"x": {"total_s": 0.5, "count": 9,
                                            "dims": [65536, 2048]}}}}
    assert moe_readers.op_time_share({}, readings, ctx) == 25.0
    assert moe_readers.op_time_share({}, {"trace": {"window_s": 2.0,
                                                    "queries": {}}},
                                     ctx) is None
    assert moe_readers.op_time_share({}, {}, ctx) is None
