"""Chaos CLI + Grafana generation tests.

Reference: `ray kill-random-node` (scripts.py:1384) and the dashboard's
grafana_dashboard_factory.py. The kill test runs REAL head/worker node
processes (python -m ray_tpu start) so process death and missed-heartbeat
discovery are genuine.
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


import pytest

pytestmark = pytest.mark.slow  # stress/e2e tier (see pytest.ini)


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    return env


def test_grafana_dashboard_generation(tmp_path):
    from ray_tpu.dashboard.grafana import (
        generate_grafana_dashboard,
        write_grafana_dashboard,
    )

    dash = generate_grafana_dashboard(extra_metric_names=["my_counter"])
    assert dash["uid"] == "ray-tpu-cluster"
    titles = [p["title"] for p in dash["panels"]]
    assert "Alive nodes" in titles and "my_counter" in titles
    exprs = [t["expr"] for p in dash["panels"] for t in p["targets"]]
    assert 'ray_tpu_cluster_resource_total{resource="TPU"}' in exprs

    path = write_grafana_dashboard(str(tmp_path / "dash.json"))
    loaded = json.load(open(path))
    assert loaded["panels"]  # valid, importable JSON


def test_kill_random_node_cli_kills_a_real_worker(tmp_path):
    head = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu", "start", "--head",
         "--num-cpus", "1", "--dashboard-port", "-1"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=_env())
    try:
        address = None
        deadline = time.time() + 60
        while time.time() < deadline and address is None:
            line = head.stdout.readline()
            if "GCS address:" in line:
                address = line.split("GCS address:")[1].strip()
        assert address, "head never printed its GCS address"

        worker = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu", "start",
             "--address", address, "--num-cpus", "1"],
            stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
            env=_env())
        try:
            # wait for the worker node to register
            check = (
                "import ray_tpu, time\n"
                f"ray_tpu.init(address='{address}')\n"
                "deadline = time.time() + 60\n"
                "while time.time() < deadline:\n"
                "    if len([n for n in ray_tpu.nodes() if n['Alive']]) >= 2:\n"
                "        break\n"
                "    time.sleep(0.5)\n"
                "else:\n"
                "    raise SystemExit('worker never joined')\n"
                "print('JOINED')\n")
            out = subprocess.run([sys.executable, "-c", check],
                                 capture_output=True, text=True, timeout=120,
                                 env=_env())
            assert "JOINED" in out.stdout, out.stderr[-2000:]

            # refusal without --yes
            out = subprocess.run(
                [sys.executable, "-m", "ray_tpu", "kill-random-node",
                 "--address", address],
                capture_output=True, text=True, timeout=120, env=_env())
            assert "pass --yes" in out.stdout
            assert worker.poll() is None  # still alive

            # the real kill: worker PROCESS must exit
            out = subprocess.run(
                [sys.executable, "-m", "ray_tpu", "kill-random-node",
                 "--address", address, "--yes"],
                capture_output=True, text=True, timeout=120, env=_env())
            assert "killed node" in out.stdout
            deadline = time.time() + 30
            while time.time() < deadline and worker.poll() is None:
                time.sleep(0.2)
            assert worker.poll() is not None, "worker process survived"
        finally:
            if worker.poll() is None:
                worker.kill()
    finally:
        head.kill()
