"""Multi-slice layout guard: model-parallel collectives must never cross
the DCN axis (VERDICT r2 weak #7).

The multi-slice doctrine (parallel/mesh.py build_multislice_mesh) puts
ONLY data parallelism across slices; tp/sp/fsdp collectives — per-layer
all-gathers, ring-attention collective-permutes, all-to-alls — must stay
on each slice's ICI. A sharding regression that silently routed tp
traffic over DCN would still produce correct numbers, just 10-100x
slower; this test pins the layout by inspecting the compiled HLO's
replica groups (pattern: tests/test_sharding_perf.py's subprocess
compile)."""

import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_HLO_SNIPPET = r"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import optax

from ray_tpu.models import llama
from ray_tpu.parallel.mesh import MeshConfig, build_multislice_mesh
from ray_tpu.parallel.sharding import LogicalAxisRules, logical_sharding
from ray_tpu.train.step import init_train_state, make_train_step

plan = {"dp": 1, "fsdp": 1, "sp": 2, "tp": 2}
mesh = build_multislice_mesh(MeshConfig(**plan), num_slices=2,
                             devices=jax.devices()[:8])
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
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 129), 0,
                          cfg.vocab_size)
batch = {"inputs": jax.device_put(toks[:, :-1], bs),
         "targets": jax.device_put(toks[:, 1:], bs)}
# make_train_step returns the jitted step: AOT-lower and dump the
# optimized HLO for replica-group inspection
compiled = step.lower(state, batch).compile()
print("===HLO START===")
print(compiled.as_text())
print("===HLO END===")
"""


def _slice_of(device_id: int) -> int:
    return 0 if device_id < 4 else 1  # dcn-outer ordering, 4 per slice


def test_no_model_collective_crosses_dcn():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-c", _HLO_SNIPPET], capture_output=True,
        text=True, timeout=900, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]
    hlo = proc.stdout.split("===HLO START===", 1)[-1]

    # model-parallel collective families: every replica group / permute
    # pair must stay inside one slice ({0..3} or {4..7}); cross-slice
    # traffic is allowed ONLY for all-reduce (the dp gradient sync)
    violations = []
    for line in hlo.splitlines():
        if re.search(r"\b(all-gather|reduce-scatter|all-to-all)\b", line):
            # the groups only: a layout such as {5,4,3,2,1,0} is not one
            groups = re.search(r"replica_groups=\{(\{[0-9,{}]*\})\}", line)
            for group in re.findall(r"\{([0-9,]+)\}",
                                    groups[1] if groups else ""):
                ids = [int(x) for x in group.split(",") if x != ""]
                if len({_slice_of(i) for i in ids}) > 1:
                    violations.append(line.strip()[:160])
        if "collective-permute" in line:
            m = re.search(r"source_target_pairs=\{(.*?)\}\s*$", line)
            pairs = re.findall(r"\{(\d+),(\d+)\}", line)
            for a, b in pairs:
                if _slice_of(int(a)) != _slice_of(int(b)):
                    violations.append(line.strip()[:160])
    assert not violations, (
        "model-parallel collectives cross the DCN axis:\n"
        + "\n".join(violations[:8]))

    # sanity: the compile actually produced within-slice model collectives
    assert re.search(r"all-gather|collective-permute|all-to-all", hlo), \
        "no collectives found — inspection snippet broke"
