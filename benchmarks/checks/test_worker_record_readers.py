"""Checks of the metrics that read what the gang worker brought home on
`finish()` (its `jit.*`, `train.init_state`, `host.gc` spans and its
counters) or on a start-up round (`train.worker.chip_wait`), of their files
and of `benchmarks/counter_readers.py`; seconds on the CPU, no chip:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/checks/test_worker_record_readers.py -q
"""

import importlib
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import counter_readers  # noqa: E402


def _span(total, own=None, longest=None, n=1):
    return {"count": n, "total_s": total,
            "max_s": total if longest is None else longest,
            "self_s": total if own is None else own}


# a made-up driver's aggregate after fit(): its own spans, rank 0's merged
SPANS = {
    "train.fit": _span(90.0, own=20.0),
    "train.worker.open_chip": _span(8.0, n=2),
    "train.worker.chip_wait": _span(6.25, longest=6.2, n=2),
    "jit.trace": _span(30.0, own=9.5, longest=6.0, n=4000),
    "jit.lower": _span(7.0, own=6.5, longest=3.0, n=40),
    "jit.compile": _span(4.0, own=0.25, longest=1.5, n=40),
    "jit.cache_load": _span(3.75, longest=1.4, n=40),
    "train.init_state": _span(2.5, own=0.5),
    "train.step.dispatch": _span(14.0, own=2.0, longest=11.0, n=130),
    "host.gc": _span(0.4, longest=0.06, n=9),
}
COUNTERS = {
    "flash.steps_unmasked": 36, "flash.steps_masked": 60,
    "flash.tiles_skipped": 180,
    "moe.gmm_tiles": 896, "moe.gmm_tiles_partial": 45,
}
READINGS = {"spans": SPANS, "counters": COUNTERS, "host": {}}
WANT = {
    "worker_jit_trace_s": 9.5, "worker_jit_lower_s": 6.5,
    "worker_jit_compile_s": 0.25, "worker_jit_cache_load_s": 3.75,
    "train_step_first_call_s": 11.0, "worker_init_state_s": 2.5,
    "worker_gc_pause_max_s": 0.06, "gang_chip_wait_s": 6.25,
    "flash_unmasked_step_share": 37.5,
    "moe_gmm_partial_tile_share": 100.0 * 45 / 896,
}


def _spec(name):
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           name + ".json")) as f:
        return json.load(f)


def _read(name, readings):
    spec = _spec(name)
    module, fn = spec["reader"].rsplit(".", 1)
    reader = getattr(importlib.import_module("benchmarks." + module), fn)
    return reader(spec, readings, {"name": name})


@pytest.mark.parametrize("name", sorted(WANT))
def test_file_names_a_reader_that_reads_a_made_up_snapshot(name):
    assert _spec(name)["note"]
    assert _read(name, READINGS) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing(name):
    # a parent commit's driver: its own spans and no counters; nothing
    other = {"spans": {"train.fit": _span(1.0)}, "counters": {"x": 1},
             "host": {}}
    assert _read(name, other) is None
    assert _read(name, {"spans": {}, "counters": {}, "host": {}}) is None


def test_ratio_wants_every_name_and_a_denominator():
    spec = {"over": ["a"], "under": ["a", "b"], "scale": 100}
    read = lambda counters: counter_readers.ratio(  # noqa: E731
        spec, {"counters": counters}, {})
    assert read({"a": 1, "b": 3}) == 25.0
    assert read({"a": 1}) is None and read({"b": 3}) is None
    assert read({"a": 0, "b": 0}) is None
    assert counter_readers.ratio({"over": ["a"], "under": ["b"]},
                                 {"counters": {"a": 1, "b": 4}}, {}) == 0.25


def test_ratio_falls_back_to_this_process(monkeypatch):
    from ray_tpu._private import device_profiler

    device_profiler.count("check.over", 2)
    device_profiler.count("check.under", 8)
    spec = {"over": ["check.over"], "under": ["check.under"]}
    assert counter_readers.ratio(spec, {}, {}) == 0.25
    # a program without the span layer: nothing to read, nothing raised
    monkeypatch.delattr(device_profiler, "snapshot")
    assert counter_readers.ratio(spec, {}, {}) is None


def test_counter_readers_imports_no_jax():
    import subprocess

    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmarks import counter_readers, span_readers; "
            "counter_readers.ratio({'over': ['a'], 'under': ['b']}, {}, {}); "
            "assert 'jax' not in sys.modules" % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True)


def test_benchmark_json_lists_them_under_cells_that_report_what_they_move():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    cells = {m["name"]: set(m.get("workloads", ()))
             for m in bench["end_to_end"]}
    every = {w["name"] for w in bench["workloads"]}
    for name in WANT:
        m = listed[name]
        assert m["source"] == ("program_counter" if name.endswith("_share")
                               else "program_span")
        assert set(m["workloads"]) <= (cells[m["moves"]] or every)
