"""Ray-Client-style proxy tests (VERDICT r1 #9).

Reference: ray util/client/server/proxier.py + ARCHITECTURE.md — remote
drivers behind an authenticated proxy, per-session isolation. The client
runs in a SUBPROCESS (a real remote driver: separate process, no direct
GCS/raylet access — the process-global worker slot is also per-process).
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


pytestmark = pytest.mark.slow  # stress/e2e tier (see pytest.ini)


@pytest.fixture()
def proxy_cluster():
    import ray_tpu
    from ray_tpu.util.client import ClientProxyServer

    ray_tpu.init(num_cpus=4)
    from ray_tpu._raylet import get_core_worker

    server = ClientProxyServer(get_core_worker().gcs_address,
                               token="sekrit-token")
    addr = server.start(0)
    yield addr
    server.stop()
    ray_tpu.shutdown()


def _run_script(script: str, *, expect_ok: bool = True):
    """One place for the subprocess-client env/timeout plumbing."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=180,
                          env=env)
    if expect_ok:
        assert proc.returncode == 0, proc.stderr[-3000:]
    return proc


def _run_client(addr: str, body: str, token: str = "sekrit-token",
                init_kwargs: str = "") -> str:
    script = textwrap.dedent(f"""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        import ray_tpu
        ray_tpu.init("client://{addr}", token={token!r}{init_kwargs})
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        ray_tpu.shutdown()
        print("CLIENT-OK")
    """)
    return _run_script(script).stdout


def test_client_tasks_put_get_wait(proxy_cluster):
    out = _run_client(proxy_cluster, """
        @ray_tpu.remote
        def add(a, b):
            return a + b

        ref = ray_tpu.put(40)
        assert ray_tpu.get(add.remote(ref, 2), timeout=60) == 42
        refs = [add.remote(i, i) for i in range(5)]
        done, pending = ray_tpu.wait(refs, num_returns=5, timeout=60)
        assert len(done) == 5 and not pending
        assert ray_tpu.get(done, timeout=60) == [0, 2, 4, 6, 8]
        print("nodes:", len(ray_tpu.nodes()))
    """)
    assert "CLIENT-OK" in out
    assert "nodes: 1" in out


def test_client_actors(proxy_cluster):
    out = _run_client(proxy_cluster, """
        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def incr(self):
                self.n += 1
                return self.n

        c = Counter.remote()
        assert ray_tpu.get([c.incr.remote() for _ in range(3)],
                           timeout=60) == [1, 2, 3]
        ray_tpu.kill(c)
    """)
    assert "CLIENT-OK" in out


def test_client_task_errors_propagate(proxy_cluster):
    out = _run_client(proxy_cluster, """
        @ray_tpu.remote(max_retries=0)
        def boom():
            raise ValueError("kaboom-777")

        try:
            ray_tpu.get(boom.remote(), timeout=60)
            raise AssertionError("should have raised")
        except Exception as e:
            assert "kaboom-777" in str(e)
    """)
    assert "CLIENT-OK" in out


def test_client_timeout_semantics_and_futures(proxy_cluster):
    """get/wait timeouts must forward to the SERVER (not become transport
    deadlines), unbounded gets must outlive the 60s RPC default setting,
    and ref.future()/await must work on client drivers."""
    out = _run_client(proxy_cluster, """
        import time
        from ray_tpu import exceptions as exc

        @ray_tpu.remote
        def slow(s):
            time.sleep(s)
            return "done"

        # wait with a short timeout returns PARTIAL, not a transport error
        ref = slow.remote(15)
        done, pending = ray_tpu.wait([ref], num_returns=1, timeout=1)
        assert not done and pending == [ref]

        # get with a short timeout raises GetTimeoutError, not RPC timeout
        try:
            ray_tpu.get(ref, timeout=1)
            raise AssertionError("should time out")
        except exc.GetTimeoutError:
            pass

        # futures resolve with the VALUE
        assert ref.future().result(timeout=60) == "done"
    """)
    assert "CLIENT-OK" in out


def test_client_job_runtime_env(proxy_cluster):
    out = _run_client(
        proxy_cluster, """
        @ray_tpu.remote
        def readenv():
            import os
            return os.environ.get("RT_CLIENT_TEST")

        print("envval=" + str(ray_tpu.get(readenv.remote(), timeout=60)))
        """,
        init_kwargs=', runtime_env={"env_vars": {"RT_CLIENT_TEST": "xyz"}}')
    assert "CLIENT-OK" in out and "envval=xyz" in out


def test_client_bad_token_rejected(proxy_cluster):
    script = textwrap.dedent(f"""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        import ray_tpu
        try:
            ray_tpu.init("client://{proxy_cluster}", token="wrong")
            print("CONNECTED")
        except ConnectionError as e:
            print("REJECTED:", e)
    """)
    proc = _run_script(script, expect_ok=False)
    assert "REJECTED" in proc.stdout and "CONNECTED" not in proc.stdout


def test_client_disallowed_method_blocked(proxy_cluster):
    out = _run_client(proxy_cluster, """
        from ray_tpu._raylet import get_core_worker

        cw = get_core_worker()
        try:
            cw._call("hold_secondary_copy", None)
            raise AssertionError("internal method must be blocked")
        except RuntimeError as e:
            assert "not allowed" in str(e)
    """)
    assert "CLIENT-OK" in out
