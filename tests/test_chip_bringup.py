"""Nothing finishes without the chip, and everyone compiles into one cache:
the cheap, chip-free half of chip_smoke.py's contract."""

import os

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_honours_the_variable_else_fixed_path(monkeypatch):
    from ray_tpu._private import compile_cache

    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.enable() == "/somewhere/else"
    assert os.environ[compile_cache.ENV_VAR] == "/somewhere/else"

    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.FIXED_DIR == os.path.join(REPO_ROOT, ".jax_cache")
    import jax

    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == compile_cache.FIXED_DIR
        # exported for spawned workers, and set on the already-imported jax
        assert os.environ[compile_cache.ENV_VAR] == compile_cache.FIXED_DIR
        assert jax.config.jax_compilation_cache_dir == compile_cache.FIXED_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_jax_backend_refuses_a_cpu_gang_that_asked_for_tpus(monkeypatch):
    """A use_tpu gang whose workers came up on another platform must not
    train on: on_start raises with what the workers reported."""
    import ray_tpu
    from ray_tpu.train.backend import (
        JaxBackend,
        JaxConfig,
        _worker_platform,
    )

    class _Method:
        def remote(self, _spanned, fn, **kw):
            # what a round hands back: the call's result, the spans it left
            return ("cpu" if fn is _worker_platform else None), {}

    class _Worker:
        execute = _Method()

    class _Gang:
        num_workers = 1
        workers = [_Worker()]
        demands_tpu = True

    monkeypatch.setattr(ray_tpu, "get", lambda refs, **kw: refs)
    with pytest.raises(RuntimeError, match=r"asked for platform 'tpu'.*cpu"):
        JaxBackend().on_start(_Gang(), JaxConfig())
    # the same gang without a TPU demand (or with its platform named) runs
    _Gang.demands_tpu = False
    JaxBackend().on_start(_Gang(), JaxConfig())
    _Gang.demands_tpu = True
    JaxBackend().on_start(_Gang(), JaxConfig(platform="cpu"))


def test_health_loop_does_not_judge_by_a_clock_it_slept_through(monkeypatch):
    """Opening four chips froze the whole v5e sandbox for 10-12 s; the GCS
    woke first and declared its (equally frozen) node dead. A round the
    loop itself overslept gives no verdict; real silence still does."""
    import asyncio
    import types

    from ray_tpu._private.ids import NodeID
    from ray_tpu._private.specs import NodeInfo
    from ray_tpu.gcs import server

    node = NodeID.from_random()
    mgr = server.GcsNodeManager.__new__(server.GcsNodeManager)
    mgr._nodes = {node: NodeInfo(node_id=node, raylet_address="x")}
    mgr._last_heartbeat = {node: 1000.0}
    dead_at = []

    async def mark_dead(node_id, expected):
        dead_at.append(clock["now"])
        mgr._nodes[node_id].alive = False

    mgr._mark_dead = mark_dead
    clock = {"now": 1000.0}
    # (seconds the loop's sleep really took, node heartbeats afterwards?)
    script = iter([(1.0, True), (12.0, True), (1.0, True)]
                  + [(1.0, False)] * 8)

    async def sleep(_period):
        await asyncio.sleep(0)  # let the previous round's heartbeat land
        try:
            nap, beats = next(script)
        except StopIteration:
            raise asyncio.CancelledError from None
        clock["now"] += nap
        if beats:  # the node was frozen too: it reports just AFTER the loop
            asyncio.get_running_loop().call_soon(
                mgr._last_heartbeat.__setitem__, node, clock["now"])

    monkeypatch.setattr(server, "time", types.SimpleNamespace(
        monotonic=lambda: clock["now"]))
    monkeypatch.setattr(server, "asyncio", types.SimpleNamespace(sleep=sleep))
    with pytest.raises(asyncio.CancelledError):
        asyncio.run(mgr.health_check_loop())
    # survived the 12 s stall (silent for 12 s > the 6.25 s limit when the
    # loop woke at 1013); died only after real silence from 1014 on
    assert len(dead_at) == 1 and dead_at[0] > 1014 + 6.25
