"""Checks of the benchmark's own arithmetic; seconds on the CPU, no chip:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/checks -q

`v5e_probe.xplane.pb.gz` is a 72 ms trace recorded on a v5e (PR 24's
probe): three steps of a 2-layer train step with the flash kernels, then
one prefill wave and one 19-step decode chunk of a tiny paged engine.
"""

import gzip
import importlib
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import loadgen, opcount, peaks, reduce_trace  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


# ------------------------------------------------------------ reduce_trace


@pytest.fixture(scope="module")
def reduced(tmp_path_factory):
    pytest.importorskip("jax")
    path = tmp_path_factory.mktemp("trace") / "probe.xplane.pb"
    with gzip.open(os.path.join(HERE, "v5e_probe.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    fwd = _json("benchmarks", "metrics", "flash_fwd_roofline.json")
    bwd = _json("benchmarks", "metrics", "flash_bwd_roofline.json")
    dec = _json("benchmarks", "metrics", "decode_step_roofline.json")
    return reduce_trace.reduce_xplane(str(path), {
        "fwd": fwd["trace_query"], "bwd": bwd["trace_query"],
        "dec": dec["trace_query"]})


def test_recorded_trace_busy_and_programs(reduced):
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx(0.072167, rel=1e-3)
    assert reduced["busy_s"] == pytest.approx(0.003856, rel=1e-3)
    mods = reduced["modules"]
    assert mods["jit_step_fn"]["count"] == 3
    assert mods["jit_step_fn"]["median_s"] == pytest.approx(1.028e-3, rel=1e-3)
    assert mods["jit_decode"]["count"] == 1
    assert mods["jit_prefill_batch"]["count"] == 1
    # self times partition the busy time: nothing is counted twice
    assert sum(s for _, s in reduced["device_ops"]) <= reduced["busy_s"]
    assert reduced["idle_gaps"][0][0] == "host:$time sleep"


def test_recorded_trace_kernels_and_decode_steps(reduced):
    q = reduced["queries"]
    # 3 steps x 2 layers x (forward + its remat in the backward)
    assert q["fwd"]["count"] == 12 and q["fwd"]["dims"] == [4, 4, 512, 128]
    # 3 steps x 2 layers x (dq kernel + dk/dv kernel)
    assert q["bwd"]["count"] == 12
    # one chunk of 19 decode steps: 692 us / 19
    assert q["dec"]["per_step_median_s"] == pytest.approx(692.18e-6 / 19,
                                                          rel=1e-3)


def test_self_times_and_union():
    events = [(0.0, 10.0, "while"), (1.0, 4.0, "a"), (5.0, 9.0, "b"),
              (6.0, 7.0, "c"), (12.0, 13.0, "d")]
    ev, selfs = reduce_trace.self_times(events)
    assert dict(zip((e[2] for e in ev), selfs)) == {
        "while": 3.0, "a": 3.0, "b": 3.0, "c": 1.0, "d": 1.0}
    assert reduce_trace.union([(5, 6), (0, 2), (1, 3)]) == [[0, 3], [5, 6]]
    assert reduce_trace.short_op(
        "%fusion.12 = bf16[4,2048]{1,0:T(8,128)} fusion(...)") \
        == "fusion_bf16_4_2048"


# ---------------------------------------------------------------- loadgen


def test_quantiles():
    v = list(range(1, 102))
    assert loadgen.quantile(v, 0.5) == 51
    assert loadgen.quantile(v, 0.9) == 91
    assert loadgen.quantile([1.0, 2.0], 0.5) == 1.5
    assert loadgen.quantile([3.0], 0.9) == 3.0


def test_one_stall_lowers_the_rate_and_shows_in_the_stall_share():
    steady = [0.5 * (i + 1) for i in range(80)]
    stalled = [t + (2.0 if i >= 40 else 0.0) for i, t in enumerate(steady)]
    a = loadgen.interval_stats(steady, 0.0, 8192, 1)
    b = loadgen.interval_stats(stalled, 0.0, 8192, 1)
    # the end-to-end rate is all tokens over all time: the stall is in it
    assert a["tokens_per_s_per_chip"] == pytest.approx(16384.0)
    assert b["tokens_per_s_per_chip"] == pytest.approx(80 * 8192 / 42.0)
    assert loadgen.interval_stats(steady, 0.0, 8192, 4)[
        "tokens_per_s_per_chip"] == pytest.approx(4096.0)
    # the median step beside it is the step's alone, and the share of the
    # window that such steps do not cover says where the rest went
    assert a["tokens_per_s_per_chip_of_median_step"] == pytest.approx(16384.0)
    assert b["tokens_per_s_per_chip_of_median_step"] == pytest.approx(16384.0)
    assert loadgen.quantile(b["step_ms"], 0.5) == pytest.approx(500.0)
    assert a["stall_share"] == pytest.approx(0.0, abs=1e-9)
    assert b["stall_share"] == pytest.approx(100 * 2.0 / 42.0)


@pytest.mark.parametrize("mix", ["chat-closed", "docs-repeat"])
def test_traffic_from_a_seed(mix):
    import random

    traffic = _json("benchmarks", "traffic", mix + ".json")
    big = 2**31 + 11
    a = loadgen.plan(traffic, big)
    assert a == loadgen.plan(traffic, big) and len(a) == traffic["clients"]

    def sizes(plan):
        asks = [x for c in plan for s in c for x in s["asks"]]
        return (sorted(s["prefix"] for c in plan for s in c),
                sorted(p for p, _ in asks), sorted(n for _, n in asks))

    # another seed: the same set of sizes in another order
    other = loadgen.plan(traffic, 5)
    assert other != a and sizes(other) == sizes(a)
    prompts = sizes(a)[1]
    assert len(prompts) == (traffic["clients"] * traffic["sessions_per_client"]
                            * traffic["asks_per_session"])
    lo, hi = traffic["prompt_tokens"]["range"]
    assert lo <= prompts[0] < lo + (hi - lo) / 8
    assert hi - (hi - lo) / 8 < prompts[-1] <= hi
    # what the engine is warmed for covers every seed's requests
    reach = loadgen.request_sizes(traffic, 64)
    assert {s["prefix"] + p for c in a + other for s in c
            for p, _ in s["asks"]} <= reach
    # no knob lets callers coordinate their sends
    assert "max_awaiting_first_token" not in traffic
    # token ids come from the seed: the same seed, the same tokens
    assert loadgen.tokens(random.Random(big), 50, 32768) \
        == loadgen.tokens(random.Random(big), 50, 32768)
    assert loadgen.tokens(random.Random(big), 50, 32768) \
        != loadgen.tokens(random.Random(5), 50, 32768)


def test_window_membership_and_client_metrics():
    def rec(t_send, t_first, t_end, got, ok=True):
        step = (t_end - t_first) / (got - 1)
        return {"t_send": t_send, "t_end": t_end, "want": got, "ok": ok,
                "t_tokens": [t_first + i * step for i in range(got)],
                "prompt": 100}

    records = [rec(9.0, 9.5, 11.0, 11),     # sent before the opening
               rec(10.0, 10.2, 12.2, 11),   # in
               rec(12.0, 12.4, 14.4, 21),   # in
               rec(19.0, 19.5, 21.0, 11)]   # sent in, ended after the close
    r = loadgen.reduce_records(records, 10.0, 20.0)
    # sent in the window = counted, however late it ended
    assert (r["attempted"], r["failed"]) == (3, 0)
    assert r["ttft_ms"] == pytest.approx([200.0, 400.0, 500.0])
    assert r["tpot_ms"] == pytest.approx([200.0, 100.0, 150.0])
    # tokens that ARRIVED in the window, whichever request they belong to:
    # 7 of the first (10.1 .. 11.0), 11, 21, and 4 of the last (19.5 .. 19.95)
    assert r["out_tokens_per_s"] == pytest.approx((7 + 11 + 21 + 4) / 10.0)
    bad = dict(rec(13.0, 13.1, 13.5, 5, ok=False), error="stream gave 5 of 9")
    r = loadgen.reduce_records(records + [bad], 10.0, 20.0)
    assert (r["attempted"], r["failed"]) == (4, 1)


# ---------------------------------------------------------------- opcount


def test_opcount_against_hand_counts():
    m = {"d_model": 4096, "n_heads": 32, "n_kv_heads": 8, "d_head": 128,
         "d_ff": 14336, "n_layers": 1, "vocab_size": 32768}
    # wq 16.78M + wk, wv 4.19M each + wo 16.78M + 3 x 58.72M
    assert opcount.layer_matmul_params(m) == 218_103_808
    assert opcount.matmul_params(m) == 218_103_808 + 134_217_728
    # 3 x (2 x 352.3M + 2 x 32 x 128 x 2048)
    assert opcount.train_flops_per_token(m, 2048) == 3 * (
        2 * 352_321_536 + 16_777_216)
    ops, nbytes = opcount.flash_fwd(4, 32, 2048, 128, 0.25)
    assert ops == 2 * 4 * 32 * 2048 * 2048 * 128
    assert nbytes == 2 * 4 * 2048 * 128 * (64 + 16)
    assert opcount.flash_bwd(4, 32, 2048, 128, 0.25)[0] == 2 * ops
    peak = peaks.peaks("TPU v5 lite")
    assert opcount.bound_seconds(ops, nbytes, peak) == ops / 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9")


# ------------------------------------------------------- BENCHMARK.json


def test_every_entry_resolves_to_files_and_legal_names():
    bench = _json("BENCHMARK.json")
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        body = _json(c["file"])
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", body["kind"] + "_cell.py"))
        for module in (body["reference"], body["opcount"]):
            assert os.path.exists(os.path.join(
                ROOT, "benchmarks", module + ".py"))
        # the model module, its config class and field map are data too
        program = body["program"]
        assert set(program) == {"module", "config_class", "fields_from",
                                "fields"}
        assert all(key in body for key in program["fields_from"].values())
    cells = set()
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and len(w["why"]) <= 200
        _json("benchmarks", "traffic", w["traffic"] + ".json")
        assert _json(configs[w["config"]]["file"])["chips"] == w["chips"]
        cells.add(w["name"])
    end_to_end = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert set(m.get("workloads", cells)) <= cells
        spec = _json("benchmarks", "metrics", m["name"] + ".json")
        module, fn = spec["reader"].rsplit(".", 1)
        assert callable(getattr(
            importlib.import_module("benchmarks." + module), fn))
    for m in bench["per_layer"]:
        moved = end_to_end[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
