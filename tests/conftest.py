"""Shared fixtures (reference pattern: ray python/ray/tests/conftest.py —
ray_start_regular :419, ray_start_cluster :500).

JAX-facing tests run on a faked 8-device CPU mesh
(xla_force_host_platform_device_count), per SURVEY §4.4: no TPU hardware is
needed to exercise sharding/collective code paths.
"""

import os
import time

# Hermetic tests: never probe the GCE metadata server for TPU topology.
os.environ.setdefault("RT_TPU_PROBE_GCE_METADATA", "0")

# Must be set before anything imports jax; spawned workers inherit both.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compilation cache: the jax-heavy tests (parallel, rllib,
# inference, models) are compile-bound; caching compiled executables
# across runs cuts the core tier's wall time roughly in half after the
# first run. Keyed by HLO + flags, so code changes that alter a program
# recompile as usual. Same helper, hence same directory, as every other
# process that compiles (ray_tpu/_private/compile_cache.py); spawned
# workers inherit it through the environment.
from ray_tpu._private import compile_cache  # noqa: E402

compile_cache.enable()
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def thread_hygiene(request):
    """Fail any test that leaves non-daemon threads or an armed chaos plan
    behind: a leaked non-daemon thread hangs the pytest process at exit,
    and a leaked chaos plan silently injects faults into every later test
    in the session. Opt out with @pytest.mark.thread_leak_ok (for tests
    that intentionally leak, e.g. to exercise this fixture)."""
    if request.node.get_closest_marker("thread_leak_ok"):
        yield
        return
    import threading

    before = set(threading.enumerate())
    yield
    from ray_tpu._private import fault_injection as fi

    leaked_plan = fi.active_plan()
    if leaked_plan is not None:
        fi.uninstall()  # disarm so later tests aren't poisoned too
        pytest.fail(
            f"test left a chaos plan armed (seed={leaked_plan.seed}, "
            f"{len(leaked_plan.rules)} rules); uninstall it in teardown "
            "(ray_tpu.chaos.uninstall() or the chaos fixture)")
    deadline = time.monotonic() + 2.0
    leaked = []
    for t in threading.enumerate():
        if t in before or t.daemon or not t.is_alive():
            continue
        t.join(timeout=max(0.05, deadline - time.monotonic()))
        if t.is_alive():
            leaked.append(t)
    if leaked:
        names = ", ".join(f"{t.name} (target={getattr(t, '_target', None)})"
                          for t in leaked)
        pytest.fail(
            f"test left {len(leaked)} non-daemon thread(s) running: "
            f"{names}; join them in teardown or mark the test "
            "thread_leak_ok")


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_2_cpus():
    import ray_tpu

    ray_tpu.init(num_cpus=2)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=False)
    yield cluster
    cluster.shutdown()
