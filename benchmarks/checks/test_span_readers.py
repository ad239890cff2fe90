"""Checks of the span readers (`benchmarks/span_readers.py`) and of the
metric files that name them; seconds on the CPU, no chip:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/checks/test_span_readers.py -q
"""

import importlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

NEW = ["cluster_init_s", "gang_place_s", "gang_backend_init_s",
       "gang_mesh_s", "gang_open_chip_s", "gang_session_launch_s",
       "trainer_start_covered_share"]


def _span(total, own=None, n=1):
    return {"count": n, "total_s": total, "max_s": total,
            "self_s": total if own is None else own}


# a made-up start of a one-chip gang: 13 s from fit() to train_fn
SPANS = {
    "cluster.init": _span(1.5),
    "train.fit": _span(60.0, own=47.2),
    "train.gang.place": _span(2.0),
    "train.gang.backend_init": _span(0.5),
    "train.gang.mesh": _span(9.0, own=0.75),   # 8.0 open_chip, 0.25 build
    "train.gang.platform_check": _span(0.1),
    "train.gang.session": _span(0.3),
    "train.gang.launch": _span(0.9),
    "train.worker.open_chip": _span(8.0, n=2),
    "train.worker.mesh_build": _span(0.25),
    "train.step.dispatch": _span(0.2, n=90),
}
READINGS = {"spans": SPANS, "host": {"trainer_start_s": 13.0}}
WANT = {
    "cluster_init_s": 1.5, "gang_place_s": 2.0, "gang_backend_init_s": 0.5,
    "gang_mesh_s": 0.75,            # self time: less what rank 0 covered
    "gang_open_chip_s": 8.0,
    "gang_session_launch_s": 0.3 + 0.9,
    "trainer_start_covered_share": 100.0 * 12.8 / 13.0,
}


def _read(name, readings):
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    module, fn = spec["reader"].rsplit(".", 1)
    reader = getattr(importlib.import_module("benchmarks." + module), fn)
    return reader(spec, readings, {"name": name})


@pytest.mark.parametrize("name", NEW)
def test_reader_on_a_made_up_snapshot(name):
    assert _read(name, READINGS) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing(name):
    # a program that timed other things, one that timed nothing
    assert _read(name, {"spans": {"other": _span(1.0)},
                        "host": {"trainer_start_s": 13.0}}) is None
    assert _read(name, {"spans": {}, "host": {}}) is None


def test_covered_share_without_the_outside_timing():
    assert _read("trainer_start_covered_share",
                 {"spans": SPANS, "host": {}}) is None


def test_reader_survives_a_program_without_the_span_layer(monkeypatch):
    """The parent commit's `device_profiler` has no `snapshot`: None, and
    no exception, so the line just leaves the metric out."""
    from ray_tpu._private import device_profiler

    monkeypatch.delattr(device_profiler, "snapshot")
    for name in NEW:
        assert _read(name, {"host": {"trainer_start_s": 13.0}}) is None


def test_benchmark_json_lists_the_new_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    tail = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == NEW
    for m in tail:
        assert m["layer"] == "trainer / gang" and m["moves"] == "setup_s"
        assert m["source"] == "program_span"
        assert m["workloads"] == ["train-1chip", "train-4chip"]
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if m["name"].endswith("_share") else
            ("s", "lower"))


@pytest.mark.parametrize("cell", ["train-1chip", "train-4chip"])
def test_rehearsal_reads_every_new_metric(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "3",
         "--trace", "1", "--rehearse"],
        env=env, capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert done.returncode == 3, done.stderr[-2000:]
    rehearsal = json.loads(done.stdout.strip().splitlines()[-1])["rehearsal"]
    readable = rehearsal["metric_was_readable"]
    assert {name: readable.get(name) for name in NEW} == {
        name: True for name in NEW}
    assert readable["trainer_start_s"] is True
