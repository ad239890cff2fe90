#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two paths the round measures, once, through the entry points a
user calls, on one cluster (`ray_tpu.init()`), with random weights made
from a seed:

- train: `JaxTrainer` in mesh-native mode takes a few AdamW steps of
  `llama.loss_fn` at the width the training benchmark uses (d_model 4096,
  32 q / 8 kv heads x 128, d_ff 14,336; depth cut to 5 layers so the
  weights and optimizer state fit one 16 GB chip), B 4 per chip x S 2048,
  over every chip of the host as one mesh.
- serve: `serve.run(build_llm_app(...), http_port=...)` with one replica
  per chip, each building `LlamaConfig.small_1b()` and its
  `PagedInferenceEngine` inside the replica, answers concurrent SSE
  requests through the HTTP proxy (three prefill buckets, one prompt
  repeated so the prefix cache is hit) and one request through the handle.

It fails — non-zero exit, no result line — unless every phase computed on
a TPU and what came out is right: Pallas custom calls in the lowered step,
finite and falling loss, no compile after the first step, flash attention
fwd+bwd agreeing with the jax.numpy reference on that device, every serve
request returning all of its tokens from a replica whose weights sit in a
TPU's memory. One process per chip: this parent never imports jax; the
train worker and the replicas are the only processes that open a chip, one
after the other.

The last line of stdout is
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import concurrent.futures
import functools
import http.client
import json
import sys
import tempfile
import time

SETTINGS = {
    "train": {
        "model": dict(
            vocab_size=32_000, d_model=4096, n_layers=5, n_heads=32,
            n_kv_heads=8, d_head=128, d_ff=14_336, max_seq_len=2048,
            loss_chunk_size=1024),
        "per_chip_batch": 4,
        "seq": 2048,
        "steps": 6,
    },
    "serve": {
        "model": None,  # LlamaConfig.small_1b()
        "max_batch": 8,
        "max_len": 1024,
        "block_size": 64,
        "max_new_tokens": 12,
        # one prompt per prefill bucket (64, 256, 1024)
        "prompt_lens": (40, 200, 600),
    },
}
# bf16 keeps 8 bits of mantissa: outputs and gradients rounded to it differ
# from a float32 reference by up to ~1e-2 of the largest element
FLASH_TOLERANCE = 2e-2
REQUEST_TIMEOUT_S = 900.0
REPLICA_START_TIMEOUT_S = 300.0


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ------------------------------------------------------------------ train


def flash_vs_reference(batch, seq, n_heads, n_kv_heads, d_head):
    """Relative error of the flash kernels (fwd + bwd, GQA, causal) against
    `_reference_attention` in float32 at full matmul precision, at the
    train step's per-chip attention shape. Runs in the train worker. The
    reference goes one batch row at a time: its [heads, S, S] score
    tensors are what the kernel exists to avoid."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import (
        _reference_attention,
        flash_attention,
    )

    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    q, do = (jax.random.normal(k, (batch, seq, n_heads, d_head),
                               jnp.bfloat16) for k in keys[:2])
    k, v = (jax.random.normal(k, (batch, seq, n_kv_heads, d_head),
                              jnp.bfloat16) for k in keys[2:])

    @jax.jit
    def flash(q, k, v, do):
        o, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
        return (o, *vjp(do))

    @jax.jit
    def reference(q, k, v, do):
        rep = n_heads // n_kv_heads

        def bhsd(x):
            return x.astype(jnp.float32).transpose(0, 2, 1, 3)

        def attend(q, k, v):
            o = _reference_attention(
                bhsd(q), bhsd(jnp.repeat(k, rep, axis=2)),
                bhsd(jnp.repeat(v, rep, axis=2)), True, d_head ** -0.5)
            return o.transpose(0, 2, 1, 3)

        with jax.default_matmul_precision("highest"):
            o, vjp = jax.vjp(attend, q, k, v)
            return (o, *vjp(do.astype(jnp.float32)))

    got = flash(q, k, v, do)
    rows = [reference(q[i:i + 1], k[i:i + 1], v[i:i + 1], do[i:i + 1])
            for i in range(batch)]
    errors = {}
    for name, g, parts in zip(("o", "dq", "dk", "dv"), got, zip(*rows)):
        want = jnp.concatenate(parts).astype(jnp.float32)
        errors[name] = float(jnp.max(jnp.abs(g.astype(jnp.float32) - want))
                             / jnp.max(jnp.abs(want)))
    return errors


def train_fn(cfg):
    """The JaxTrainer worker's loop: it owns every chip of the host."""
    import time
    from functools import partial

    import jax
    import numpy as np
    import optax

    from ray_tpu import train
    from ray_tpu._private.device_profiler import (
        compile_stats,
        hbm_stats,
        install_compile_listener,
    )
    from ray_tpu.models import llama
    from ray_tpu.parallel.sharding import LogicalAxisRules

    install_compile_listener()
    mesh = train.get_mesh()
    devices = jax.devices()
    model = llama.LlamaConfig(**cfg["model"])
    rules = LogicalAxisRules()
    opt = optax.adamw(3e-4, weight_decay=0.0)
    state, shardings = train.init_train_state(
        partial(llama.init, model), opt, llama.param_logical_axes(model),
        mesh, jax.random.PRNGKey(0), rules)
    bs = train.batch_sharding(mesh, rules)
    step = train.make_train_step(
        partial(llama.loss_fn, config=model, mesh=mesh, rules=rules),
        opt, shardings, batch_sharding={"inputs": bs, "targets": bs})
    batch, seq = cfg["per_chip_batch"] * len(devices), cfg["seq"]
    toks = jax.random.randint(
        jax.random.PRNGKey(1), (batch, seq + 1), 0, model.vocab_size)
    b = {"inputs": jax.device_put(toks[:, :-1], bs),
         "targets": jax.device_put(toks[:, 1:], bs)}
    pallas_in_hlo = "tpu_custom_call" in step.lower(state, b).as_text()

    losses, step_s = [], []
    for i in range(cfg["steps"]):  # one repeated batch: the loss must fall
        t0 = time.perf_counter()
        state, m = step(state, b)
        losses.append(float(m["loss"]))  # the host transfer is the fence
        step_s.append(round(time.perf_counter() - t0, 3))
        if i == 0:
            compiles_after_first_step = compile_stats()["compiles"]
    compiles_after_last_step = compile_stats()["compiles"]
    hbm = hbm_stats(export=False)

    # tp rides the fastest links only if each tp group is a chain of
    # physical neighbours (build_mesh reshapes jax.devices() in list order)
    tp_groups = np.moveaxis(
        mesh.devices, mesh.axis_names.index("tp"), -1
    ).reshape(-1, mesh.shape["tp"])
    tp_neighbours = all(
        sum(abs(p - q) for p, q in zip(getattr(x, "coords", (x.id,)),
                                       getattr(y, "coords", (y.id,)))) == 1
        for group in tp_groups for x, y in zip(group, group[1:]))

    del state, b  # make room for the reference attention
    flash_errors = flash_vs_reference(
        cfg["per_chip_batch"], seq, model.n_heads, model.n_kv_heads,
        model.d_head)

    train.report({
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "mesh": {a: int(n) for a, n in mesh.shape.items() if n > 1},
        "device_coords": [list(getattr(d, "coords", ())) for d in devices],
        "tp_neighbours": tp_neighbours,
        "pallas_in_hlo": pallas_in_hlo,
        "losses": [round(x, 4) for x in losses],
        "step_s": step_s,
        "compiles_after_first_step": compiles_after_first_step,
        "compiles_after_last_step": compiles_after_last_step,
        "compile_s": round(compile_stats()["compile_s"], 2),
        "peak_bytes_in_use": max(
            (s.get("peak_bytes_in_use", 0) for s in hbm.values()), default=0),
        "flash_rel_err": {k: round(v, 5) for k, v in flash_errors.items()},
    })


def train_phase(chips: int) -> dict:
    from ray_tpu.parallel.mesh import MeshConfig, axis_plan
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cfg = SETTINGS["train"]
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as storage:
        result = JaxTrainer(
            train_fn,
            train_loop_config=cfg,
            mesh_config=MeshConfig(**axis_plan(chips)),
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True,
                resources_per_worker={"TPU": chips}),
            run_config=RunConfig(name="chip_smoke", storage_path=storage),
        ).fit()
    if result.error is not None:
        raise result.error
    m = result.metrics
    print(json.dumps({"phase": "train", **m}), flush=True)
    losses = m["losses"]
    _check(m["platform"] == "tpu",
           f"train worker came up on {m['platform']}, not tpu")
    _check(m["device_count"] == chips,
           f"train worker sees {m['device_count']} devices, host has {chips}")
    _check(m["pallas_in_hlo"],
           "no tpu_custom_call in the lowered train step: flash attention "
           "did not take the Pallas path")
    _check(len(losses) >= 5 and all(x == x and abs(x) != float("inf")
                                    for x in losses),
           f"losses not finite: {losses}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    _check(m["compiles_after_last_step"] == m["compiles_after_first_step"],
           "a compile happened after the first step")
    _check(m["tp_neighbours"],
           f"a tp group spans non-neighbouring chips: {m['device_coords']}")
    _check(max(m["flash_rel_err"].values()) < FLASH_TOLERANCE,
           f"flash attention disagrees with the reference: "
           f"{m['flash_rel_err']}")
    return m


# ------------------------------------------------------------------ serve


def build_engine(cfg):
    """Runs inside the serve replica: weights are made on its chip."""
    import jax

    from ray_tpu._private.device_profiler import install_compile_listener
    from ray_tpu.inference.paged_engine import PagedInferenceEngine
    from ray_tpu.models import llama

    install_compile_listener()
    model = (llama.LlamaConfig(**cfg["model"]) if cfg["model"]
             else llama.LlamaConfig.small_1b())
    return PagedInferenceEngine(
        llama.init(model, jax.random.PRNGKey(0)), model,
        max_batch=cfg["max_batch"], max_len=cfg["max_len"],
        block_size=cfg["block_size"])


def sse_tokens(conn: http.client.HTTPConnection, body: dict) -> list:
    """One request through the HTTP proxy; the token ids of its SSE stream."""
    conn.request("POST", "/", body=json.dumps(body),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    text = resp.read().decode()
    _check(resp.status == 200, f"HTTP {resp.status}: {text[:500]}")
    events = [line[len("data: "):] for line in text.splitlines()
              if line.startswith("data: ")]
    _check(bool(events) and events[-1] == "[DONE]",
           f"SSE stream did not end with [DONE]: {text[-300:]}")
    return [e["token"] for e in map(json.loads, events[:-1]) if "token" in e]


def serve_phase(chips: int) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private.rpc import find_free_port
    from ray_tpu.serve.llm import build_llm_app

    cfg = SETTINGS["serve"]
    max_new = cfg["max_new_tokens"]
    port = find_free_port()
    app = build_llm_app(
        functools.partial(build_engine, cfg), name="llm",
        num_replicas=chips, default_config={"max_new_tokens": max_new},
        engine_actor_options={"resources": {"TPU": 1}})
    handle = serve.run(app, name="llm", http_port=port)
    controller = ray_tpu.get_actor("SERVE_CONTROLLER")

    def replicas() -> list:
        return ray_tpu.get(controller.get_replica_handles.remote(
            "llm", "llm_engine"))

    # serve.run returns once ONE replica is up; every chip should serve
    deadline = time.monotonic() + REPLICA_START_TIMEOUT_S
    while len(replicas()) < chips:
        _check(time.monotonic() < deadline,
               f"only {len(replicas())} of {chips} replicas came up")
        time.sleep(1.0)

    def prompt(i: int, n: int) -> list:
        return [1 + (7 * i + j) % 997 for j in range(n)]

    def over_http(bodies: list) -> list:
        # one keep-alive connection: the repeat lands on the proxy shard
        # (hence the session table, hence the replica) its original did
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=REQUEST_TIMEOUT_S)
        try:
            return [sse_tokens(conn, body) for body in bodies]
        finally:
            conn.close()

    def over_handle(body: dict) -> list:
        return list(handle.options(method_name="stream_tokens",
                                   stream=True).remote(body))

    # Per chip, concurrently: one prompt per prefill bucket over HTTP, the
    # longest of them asked again once answered (a prefix-cache hit, kept
    # on its replica by the session id); plus one through the handle.
    streams = []
    with concurrent.futures.ThreadPoolExecutor(max_workers=16) as pool:
        for c in range(chips):
            bodies = [{"prompt": prompt(3 * c + i, n)}
                      for i, n in enumerate(cfg["prompt_lens"])]
            again = dict(bodies[-1], session_id=f"chip-smoke-{c}")
            streams += [pool.submit(over_http, [b]) for b in bodies[:-1]]
            streams.append(pool.submit(over_http, [again, again]))
        streams.append(pool.submit(
            lambda: [over_handle({"prompt": prompt(99, 24)})]))
        answers = [toks for f in streams for toks in f.result()]
    _check(all(len(toks) == max_new for toks in answers),
           f"requests did not return {max_new} tokens each: "
           f"{[len(t) for t in answers]}")

    stats = [ray_tpu.get(r.handle_request.remote("get_stats", (), {}),
                         timeout=60) for r in replicas()]
    serve.shutdown()
    engines = [s["engine"] for s in stats]
    devices = [e["device"] for e in engines]
    m = {
        "platform": devices[0]["platform"],
        "device_kind": devices[0]["device_kind"],
        "device_count": len(devices),
        "visible_chips": [d["visible_chips"] for d in devices],
        "requests": len(answers),
        "finished_per_replica": [s["finished_requests"] for s in stats],
        "prefix_hit_requests": sum(
            e["prefix_cache"]["hit_requests"] for e in engines),
        "compile_s": round(max(e["compile"]["compile_s"] for e in engines),
                           2),
        "param_bytes": engines[0]["param_bytes"],
        "bytes_in_use": [d.get("bytes_in_use") for d in devices],
        "peak_bytes_in_use": max(
            d.get("peak_bytes_in_use") or 0 for d in devices),
    }
    print(json.dumps({"phase": "serve", **m}), flush=True)
    _check(len(devices) == chips,
           f"{len(devices)} replicas answered get_stats, expected {chips}")
    for d, e in zip(devices, engines):
        _check(d["platform"] == "tpu" and "TPU" in d["device_kind"],
               f"replica computes on {d['platform']} {d['device_kind']!r}")
        _check((d.get("bytes_in_use") or 0) >= e["param_bytes"],
               f"replica's device holds {d.get('bytes_in_use')} bytes, "
               f"less than its {e['param_bytes']} bytes of weights")
    _check(len(set(m["visible_chips"])) == chips,
           f"replicas share a chip: TPU_VISIBLE_CHIPS {m['visible_chips']}")
    _check(sum(m["finished_per_replica"]) == len(answers),
           f"replicas finished {m['finished_per_replica']} of "
           f"{len(answers)} requests")
    _check(m["prefix_hit_requests"] >= 1,
           "the repeated prompt did not hit the prefix cache")
    return m


def main() -> None:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import compile_cache

    print(json.dumps({"compile_cache": compile_cache.enable()}), flush=True)
    ray_tpu.init()
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        _check(chips >= 1, "the node advertises no TPU chips")
        train = train_phase(chips)
        # the trainer killed its worker; the replicas' leases are granted
        # only once that process is gone and its chips are free again
        serve_phase(chips)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    _check("jax" not in sys.modules, "the smoke's parent imported jax")
    print(json.dumps({"ok": True, "device": {
        "platform": train["platform"], "kind": train["device_kind"],
        "count": train["device_count"]}}), flush=True)


if __name__ == "__main__":
    main()
