"""Perf-shape regression tests for the multichip sharding layout.

Asserts the sp/tp/fsdp train step compiles WITHOUT XLA's "[SPMD] Involuntary
full rematerialization" warning — the replicate-then-repartition fallback the
SPMD partitioner emits when a reshard has no efficient lowering (a bandwidth
cliff on a real slice). VERDICT r1 flagged two such warnings on the embedding
gather; this test pins the fix (models/llama.py forward_hidden constrains the
table's embed dim to the activation layout before the lookup).

Runs the compile in a subprocess so the C++-level stderr warning can be
captured (it bypasses Python's sys.stderr).
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_COMPILE_SNIPPET = r"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models import llama
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.parallel.sharding import LogicalAxisRules, logical_sharding
from ray_tpu.train.step import init_train_state, make_train_step

plan = {"dp": 1, "fsdp": 2, "sp": 2, "tp": 2}
mesh = build_mesh(MeshConfig(**plan), devices=jax.devices()[:8])
cfg = dataclasses.replace(
    llama.LlamaConfig.tiny(), use_ring_attention=True, dtype=jnp.float32)
rules = LogicalAxisRules()
opt = optax.adamw(1e-3)
state, shardings = init_train_state(
    partial(llama.init, cfg), opt, llama.param_logical_axes(cfg),
    mesh, jax.random.PRNGKey(0), rules)
bs = logical_sharding(mesh, ("batch", "seq"), rules)
step = make_train_step(
    partial(llama.loss_fn, config=cfg, mesh=mesh, rules=rules),
    opt, shardings, batch_sharding={"inputs": bs, "targets": bs})
toks = jax.random.randint(jax.random.PRNGKey(1), (2, 129), 0, cfg.vocab_size)
batch = {"inputs": jax.device_put(toks[:, :-1], bs),
         "targets": jax.device_put(toks[:, 1:], bs)}
state, metrics = step(state, batch)
jax.block_until_ready(metrics["loss"])
print("COMPILED_OK", float(metrics["loss"]))
"""


def test_multichip_step_compiles_without_involuntary_remat():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-c", _COMPILE_SNIPPET],
        capture_output=True, text=True, cwd=REPO, env=env, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "COMPILED_OK" in proc.stdout
    combined = proc.stdout + proc.stderr
    assert "Involuntary full rematerialization" not in combined, (
        "SPMD partitioner fell back to replicate-then-repartition:\n"
        + combined[-4000:]
    )
