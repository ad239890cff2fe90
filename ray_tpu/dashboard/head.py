"""Dashboard head: HTTP state API + Prometheus metrics.

Reference: ray dashboard/head.py (aiohttp app with pluggable modules —
job/state/reporter/metrics) + the per-node metrics agent's Prometheus
exposition (_private/metrics_agent.py). This implementation is a stdlib
threaded HTTP server talking straight to the GCS, so it runs standalone on
the head node with zero extra dependencies.

Endpoints:
  GET /                     tiny HTML overview
  GET /api/cluster_status   nodes + resource totals/available + demands
  GET /api/nodes|actors|jobs|placement_groups|tasks|workers
  GET /api/version
  GET /api/metrics_timeseries  ring-buffered time series for the SPA's
                               live metrics page (task throughput, stage
                               latency percentiles, store bytes, node CPU)
  GET /metrics              Prometheus exposition (user metrics + core gauges)
"""

from __future__ import annotations

import json
import logging
import threading
import time
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

from ray_tpu._private.rpc import ClientPool, EventLoopThread, RpcClient

logger = logging.getLogger(__name__)

# time-series ring buffers: one hour at the 5s background cadence
TS_MAXLEN = 720
TS_SAMPLE_PERIOD_S = 5.0
TS_MIN_SAMPLE_GAP_S = 1.0  # on-demand endpoint sampling floor


class DashboardHead:
    def __init__(self, gcs_address: str, host: str = "127.0.0.1",
                 port: int = 8265):
        self.gcs_address = gcs_address
        self._lt = EventLoopThread("dashboard")
        self._gcs = RpcClient(gcs_address, self._lt)
        self._raylets = ClientPool(self._lt)  # reused across /api/logs calls
        self._jobs_lock = threading.Lock()
        self._jobs_sdk = None
        dash = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # noqa: N802 — quiet
                pass

            def do_GET(self):  # noqa: N802 — http.server API
                try:
                    dash._route(self)
                except BrokenPipeError:
                    pass
                except Exception as e:  # noqa: BLE001
                    logger.exception("dashboard request failed")
                    try:
                        self.send_error(500, str(e))
                    except Exception:  # noqa: BLE001
                        pass

            def do_POST(self):  # noqa: N802 — http.server API
                try:
                    dash._route_post(self)
                except BrokenPipeError:
                    pass
                except Exception as e:  # noqa: BLE001
                    logger.exception("dashboard POST failed")
                    try:
                        self.send_error(500, str(e))
                    except Exception:  # noqa: BLE001
                        pass

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self._httpd.daemon_threads = True
        self.url = f"http://{host}:{self._httpd.server_address[1]}"
        # Live metrics time series: a background sampler fills ring
        # buffers; the endpoint also samples on demand so a freshly-polled
        # page never sees an empty window. State must exist BEFORE the
        # HTTP thread starts serving, or a scrape racing startup 500s.
        self._ts_lock = threading.Lock()       # ring-buffer reads/writes
        self._ts_sampling = threading.Lock()   # one sampler at a time
        self._ts: Dict[str, deque] = {}
        self._ts_last_sample = 0.0
        # sampler health (ISSUE 20 satellite): a failed sample used to be
        # a debug log + a last point persisting indefinitely — now every
        # failure is counted, surfaced in /api/metrics_timeseries, and
        # logged at warning (rate-limited) so "flat" and "dead" are
        # distinguishable
        self._ts_last_success = 0.0
        self._ts_fail_count = 0
        self._ts_last_warn = 0.0
        self._ts_tp_prev_t: Optional[float] = None
        self._ts_finished_cum = 0
        self._ts_event_watermarks: Dict[str, float] = {}
        self._ts_stop = threading.Event()
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="dashboard-http",
            daemon=True)
        self._thread.start()
        self._ts_thread = threading.Thread(
            target=self._ts_loop, name="dashboard-ts", daemon=True)
        self._ts_thread.start()

    # -- routing -------------------------------------------------------------

    # -- job submission REST API (reference: dashboard/modules/job/
    # job_head.py — POST/GET /api/jobs/) ------------------------------------

    def _jobs_client(self):
        """Lazy driver connection for the submission API: actor calls need
        a core worker, which `start --head` processes don't have until the
        first job request arrives. Locked: ThreadingHTTPServer handlers run
        concurrently and double-init raises."""
        with self._jobs_lock:
            if self._jobs_sdk is None:
                import ray_tpu
                from ray_tpu.job_submission import JobSubmissionClient

                if not ray_tpu.is_initialized():
                    ray_tpu.init(address=self.gcs_address)
                self._jobs_sdk = JobSubmissionClient()
            return self._jobs_sdk

    @staticmethod
    def _job_json(details) -> Dict[str, Any]:
        return {
            "submission_id": details.submission_id,
            "entrypoint": details.entrypoint,
            "status": details.status.value,
            "message": details.message,
            "metadata": details.metadata,
            "runtime_env": details.runtime_env,
            "start_time": details.start_time,
            "end_time": details.end_time,
            "driver_exit_code": details.driver_exit_code,
        }

    def _route_jobs_get(self, req, parts) -> None:
        client = self._jobs_client()
        if not parts:  # GET /api/jobs/  — list submissions
            self._json(req, [self._job_json(d)
                             for d in client.list_jobs()])
        elif len(parts) == 1:  # GET /api/jobs/<sid>
            try:
                details = client.get_job_info(parts[0])
            except RuntimeError:
                req.send_error(404, f"job {parts[0]!r} not found")
                return
            self._json(req, self._job_json(details))
        elif len(parts) == 2 and parts[1] == "logs":
            # ?offset=N: the manager seeks past N bytes — neither the actor
            # RPC nor the HTTP response carries the already-seen prefix
            from urllib.parse import parse_qs, urlparse

            q = parse_qs(urlparse(req.path).query)
            offset = int(q.get("offset", ["0"])[0])
            text, end = client.get_job_logs_from(parts[0], offset)
            self._json(req, {"logs": text, "total_len": end})
        else:
            req.send_error(404)

    def _route_post(self, req: BaseHTTPRequestHandler) -> None:
        path = req.path.split("?")[0].rstrip("/")
        length = int(req.headers.get("Content-Length") or 0)
        body = json.loads(req.rfile.read(length) or b"{}") if length else {}
        if path == "/api/jobs":
            if not body.get("entrypoint"):
                req.send_error(400, "missing required field 'entrypoint'")
                return
            client = self._jobs_client()
            sid = client.submit_job(
                entrypoint=body["entrypoint"],
                submission_id=body.get("submission_id"),
                runtime_env=body.get("runtime_env"),
                metadata=body.get("metadata"))
            self._json(req, {"submission_id": sid})
        elif path.startswith("/api/jobs/") and path.endswith("/stop"):
            sid = path[len("/api/jobs/"):-len("/stop")]
            self._json(req, {"stopped": self._jobs_client().stop_job(sid)})
        else:
            req.send_error(404)

    def _route(self, req: BaseHTTPRequestHandler) -> None:
        path = req.path.split("?")[0].rstrip("/") or "/"
        # submission API: /api/jobs/<...> (GET /api/jobs without a subpath
        # keeps serving cluster job info from the GCS, like /api/nodes)
        if path.startswith("/api/jobs/") or (
                req.path.split("?")[0] == "/api/jobs/"):
            self._route_jobs_get(
                req, [p for p in path[len("/api/jobs/"):].split("/") if p])
            return
        if path == "/":
            html = self._client_file("index.html")
            if html is not None:
                self._respond(req, html, "text/html")
            else:  # packaged frontend missing: keep the minimal overview
                self._respond(req, self._index_html(), "text/html")
        elif path.startswith("/static/"):
            name = path[len("/static/"):]
            body = self._client_file(name)
            if body is None:
                req.send_error(404)
            else:
                ctype = ("text/css" if name.endswith(".css")
                         else "application/javascript"
                         if name.endswith(".js") else "text/plain")
                self._respond(req, body, ctype)
        elif path == "/api/serve":
            self._json(req, self._serve_status(req))
        elif path == "/api/logs":
            # worker log tails, fanned out over each raylet's
            # tail_worker_logs RPC (reference: dashboard log routes)
            from urllib.parse import parse_qs, urlparse

            q = parse_qs(urlparse(req.path).query)
            self._json(req, self._worker_logs(
                lines=int(q.get("lines", ["100"])[0]),
                node_id=(q.get("node_id", [None])[0])))
        elif path == "/api/metrics_timeseries":
            self._json(req, self._timeseries())
        elif path == "/api/health":
            # cluster health plane (ISSUE 20): scorecard + firing alerts
            # + demand signals, straight from the GCS metrics manager
            self._json(req, self._gcs.call("get_health", {}, timeout=10))
        elif path == "/api/alerts":
            self._json(req, self._gcs.call("get_alerts", {}, timeout=10))
        elif path == "/metrics":
            self._respond(req, self._metrics_text(),
                          "text/plain; version=0.0.4")
        elif path == "/api/version":
            self._json(req, {"ray_version": "ray_tpu-0.1",
                             "gcs_address": self.gcs_address})
        elif path == "/api/cluster_status":
            self._json(req, self._cluster_status())
        elif path == "/api/timeline":
            # chrome-trace task timeline (load in Perfetto / chrome://tracing,
            # or the SPA's Timeline page)
            from ray_tpu.util.state.api import build_chrome_trace

            events = self._gcs.call(
                "get_task_events", {"job_id": None, "limit": 100_000},
                timeout=30)
            self._json(req, build_chrome_trace(events))
        elif path == "/api/trace":
            # distributed-request trace lookup (ISSUE 11): ?trace_id=<id>
            # returns the cross-process span set + a rendered tree +
            # the lifecycle events stamped with the id; without trace_id,
            # recent sampled/force-kept trace summaries (the SPA's Trace
            # page and curl both consume this)
            from urllib.parse import parse_qs, urlparse

            from ray_tpu._private.tracing import format_trace, trace_chrome

            q = parse_qs(urlparse(req.path).query)
            trace_id = q.get("trace_id", [None])[0]
            if not trace_id:
                self._json(req, {
                    "traces": self._gcs.call(
                        "list_traces",
                        {"limit": int(q.get("limit", ["50"])[0])},
                        timeout=30)})
            else:
                reply = self._gcs.call(
                    "get_trace", {"trace_id": trace_id}, timeout=30)
                spans = reply.get("spans") or []
                reply["tree"] = format_trace(spans) if spans else ""
                if q.get("chrome", [None])[0]:
                    reply["chrome"] = trace_chrome(spans)
                reply["events"] = self._gcs.call(
                    "get_cluster_events",
                    {"limit": 1000, "trace_id": trace_id}, timeout=30)
                self._json(req, reply)
        elif path == "/api/events":
            # cluster-wide lifecycle event feed (same filters as the
            # `ray-tpu events` CLI: type glob + id exact-matches)
            from urllib.parse import parse_qs, urlparse

            q = parse_qs(urlparse(req.path).query)

            def _one(key):
                return q.get(key, [None])[0]

            self._json(req, {
                "events": self._gcs.call("get_cluster_events", {
                    "limit": int(_one("limit") or 1000),
                    "type": _one("type"), "task_id": _one("task_id"),
                    "actor_id": _one("actor_id"),
                    "node_id": _one("node_id")}, timeout=30),
                "stats": self._gcs.call("get_event_log_stats", {},
                                        timeout=30),
            })
        elif path == "/api/agents":
            self._json(req, self._agents())
        elif path.startswith("/api/nodes/") and path.count("/") >= 4:
            # per-node agent proxy: /api/nodes/<node_id>/<stats|logs|profile>
            _, _, _, node_id, sub = path.split("/", 4)
            self._proxy_agent(req, node_id, sub)
        elif path.startswith("/api/nodes/"):
            req.send_error(
                404, "expected /api/nodes/<node_id>/<stats|logs|profile>")
        elif path.startswith("/api/"):
            kind = path[len("/api/"):]
            data = self._list(kind)
            if data is None:
                req.send_error(404, f"unknown resource {kind!r}")
            else:
                self._json(req, data)
        else:
            req.send_error(404)

    def _agents(self) -> Dict[str, str]:
        """node_id -> agent http url, from the agents' KV registrations."""
        from ray_tpu.dashboard.agent import AGENT_KV_PREFIX

        out: Dict[str, str] = {}
        try:
            keys = self._gcs.call(
                "kv_keys", {"prefix": AGENT_KV_PREFIX}, timeout=10)
            vals = self._gcs.call(
                "kv_multi_get", {"keys": list(keys)}, timeout=10)
        except Exception:  # noqa: BLE001 — no agents registered
            return out
        for key, val in (vals or {}).items():
            if val is None:
                continue
            k = key.decode() if isinstance(key, bytes) else key
            v = val.decode() if isinstance(val, bytes) else val
            out[k[len(AGENT_KV_PREFIX):]] = v
        return out

    def _proxy_agent(self, req, node_id: str, sub: str) -> None:
        """Forward /api/nodes/<id>/<sub>?... to that node's agent
        (reference: the head's DataOrganizer pulling per-node agent data)."""
        import urllib.request
        from urllib.parse import urlparse

        url = self._agents().get(node_id)
        if url is None:
            req.send_error(404, f"no agent registered for node {node_id}")
            return
        query = urlparse(req.path).query
        target = f"{url}/api/local/{sub}" + (f"?{query}" if query else "")
        try:
            with urllib.request.urlopen(target, timeout=60) as resp:
                self._respond(req, resp.read().decode(), "application/json")
        except Exception as e:  # noqa: BLE001 — agent down
            req.send_error(502, f"agent unreachable: {e}")

    def _respond(self, req, body: str, ctype: str) -> None:
        data = body.encode()
        req.send_response(200)
        req.send_header("Content-Type", ctype)
        req.send_header("Content-Length", str(len(data)))
        req.end_headers()
        req.wfile.write(data)

    def _json(self, req, obj: Any) -> None:
        self._respond(req, json.dumps(obj, default=str), "application/json")

    @staticmethod
    def _client_file(name: str) -> Optional[str]:
        """Read a packaged frontend file (dashboard/client/) — no build
        step, no extra server: the same stdlib handler serves the SPA
        (reference capability: dashboard/client/src React app)."""
        import os

        base = os.path.join(os.path.dirname(__file__), "client")
        path = os.path.normpath(os.path.join(base, name))
        # trailing separator: plain startswith(base) would admit sibling
        # paths like .../client_extra
        if not path.startswith(base + os.sep):  # no traversal
            return None
        try:
            with open(path, encoding="utf-8") as f:
                return f.read()
        except OSError:
            return None

    def _serve_status(self, req) -> Dict[str, Any]:
        """Serve application/deployment states for the Serve page, plus
        the control plane's FT posture (ISSUE 12): controller
        incarnation, checkpoint freshness, and the last recovery's
        adopted-vs-restarted replica split."""
        self._jobs_client()  # ensures a connected driver
        from ray_tpu.serve import api as serve_api

        try:
            out: Dict[str, Any] = {"applications": serve_api.status()}
        except Exception:  # noqa: BLE001 — serve not running
            return {"applications": {}}
        try:
            import ray_tpu
            from ray_tpu.serve.context import CONTROLLER_NAME

            controller = ray_tpu.get_actor(CONTROLLER_NAME)
            out["controller"] = ray_tpu.get(
                controller.get_recovery_info.remote(), timeout=5)
        except Exception:  # noqa: BLE001 — controller down mid-recovery
            pass
        return out

    # -- data ----------------------------------------------------------------

    def _worker_logs(self, lines: int = 100,
                     node_id: Optional[str] = None) -> Dict[str, Any]:
        from ray_tpu.util.state.api import collect_worker_logs

        # short per-node timeout: one wedged raylet must not stall the
        # whole fan-out (calls are sequential on this thread)
        return collect_worker_logs(
            self._gcs.call("get_all_node_info", {}, timeout=10),
            lambda addr, payload: self._raylets.get(addr).call(
                "tail_worker_logs", payload, timeout=5),
            node_id=node_id, lines=lines)

    def _cluster_status(self) -> Dict[str, Any]:
        load = self._gcs.call("get_cluster_load", {}, timeout=10)
        total: Dict[str, float] = {}
        avail: Dict[str, float] = {}
        for n in load["nodes"].values():
            if not n["alive"]:
                continue
            for k, v in n["total"].items():
                total[k] = total.get(k, 0.0) + v
            for k, v in n["available"].items():
                avail[k] = avail.get(k, 0.0) + v
        return {"nodes": load["nodes"], "resources_total": total,
                "resources_available": avail,
                "pending_demands": load.get("demands", []),
                "pending_pg_bundles": load.get("pending_pg_bundles", [])}

    def _list(self, kind: str) -> Optional[list]:
        if kind == "nodes":
            infos = self._gcs.call("get_all_node_info", {}, timeout=10)
            return [{
                "node_id": n.node_id.hex(),
                "state": "ALIVE" if n.alive else "DEAD",
                "raylet_address": n.raylet_address,
                "resources_total": dict(n.resources_total),
                "resources_available": dict(n.resources_available),
                "is_head_node": n.is_head,
            } for n in infos]
        if kind == "actors":
            actors = self._gcs.call("list_actors", {}, timeout=10)
            return [{
                "actor_id": a.actor_id.hex(),
                "state": getattr(a.state, "name", str(a.state)),
                "name": a.name or "",
                "class_name": a.class_name,
                "pid": a.pid,
                "restarts": a.num_restarts,
            } for a in actors]
        if kind == "jobs":
            jobs = self._gcs.call("get_all_job_info", {}, timeout=10)
            return [{
                "job_id": j.job_id.hex() if hasattr(j.job_id, "hex")
                else str(j.job_id),
                "is_dead": j.is_dead,
                "driver_address": j.driver_address,
            } for j in jobs]
        if kind == "placement_groups":
            pgs = self._gcs.call("list_placement_groups", {}, timeout=10)
            return pgs
        if kind == "tasks":
            events = self._gcs.call(
                "get_task_events", {"job_id": None, "limit": 10_000},
                timeout=10)
            from ray_tpu.util.state.api import latest_task_events

            return list(latest_task_events(events).values())
        if kind == "workers":
            from ray_tpu.util.state import list_workers

            try:
                return list_workers()
            except Exception:  # noqa: BLE001 — needs a connected worker
                return []
        return None

    # -- live metrics time series -------------------------------------------

    def _ts_loop(self) -> None:
        while not self._ts_stop.wait(TS_SAMPLE_PERIOD_S):
            try:
                self._ts_sample()
            except Exception:  # noqa: BLE001 — sampler must never die
                self._ts_fail_count += 1
                now = time.time()
                if now - self._ts_last_warn > 60.0:
                    self._ts_last_warn = now
                    logger.warning(
                        "timeseries sample failed (%d consecutive; series "
                        "are going stale, last success %.0fs ago)",
                        self._ts_fail_count,
                        now - self._ts_last_success
                        if self._ts_last_success else -1.0,
                        exc_info=True)

    def _ts_add(self, name: str, t: float, value: float) -> None:
        buf = self._ts.get(name)
        if buf is None:
            buf = self._ts[name] = deque(maxlen=TS_MAXLEN)
        buf.append((round(t, 3), value))

    def _ts_sample(self) -> None:
        """Collect one point of every series. Sources: the process-local
        metrics registry (stage-latency histograms — the head runs in the
        driver process for in-process clusters), GCS task events (task
        throughput), per-raylet node stats (store bytes, leases), and
        dashboard agents (per-node CPU). Every source is best-effort.

        The cluster fan-out can block for seconds (per-node RPCs with
        nodes mid-death), so it runs OUTSIDE _ts_lock — holding it here
        would hang every /api/metrics_timeseries request on the HTTP
        threads. _ts_sampling serializes samplers instead (an on-demand
        request racing the background loop simply skips; the buffers are
        at most one cycle stale)."""
        if not self._ts_sampling.acquire(blocking=False):
            return
        try:
            now = time.time()
            if now - self._ts_last_sample < TS_MIN_SAMPLE_GAP_S:
                return
            points: list = []
            self._ts_collect(now, points)
            with self._ts_lock:
                self._ts_last_sample = now
                self._ts_last_success = now
                self._ts_fail_count = 0
                for name, value in points:
                    self._ts_add(name, now, value)
            # Ship the collected points to the GCS health store (ISSUE
            # 20): the dashboard ring becomes a warm cache over the
            # cluster-wide store, so series survive dashboard restarts.
            # Tagged src=dash so _timeseries can query exactly its own
            # families back without pattern-matching names.
            if points:
                try:
                    import os as _os

                    self._gcs.send("push_metrics", {
                        "source": "dashboard", "pid": _os.getpid(),
                        "time": now,
                        "points": [[name, {"src": "dash"}, float(v)]
                                   for name, v in points]})
                except Exception:  # noqa: BLE001 — GCS mid-restart
                    logger.debug("metric push failed", exc_info=True)
        finally:
            self._ts_sampling.release()

    def _ts_collect(self, now: float, points: list) -> None:
        """Gather one (name, value) point per series into `points`.
        Runs unlocked — must not touch the ring buffers."""
        add = lambda name, value: points.append((name, value))  # noqa: E731
        # 1) stage-latency percentiles from the local metrics registry
        from ray_tpu.util.metrics import get_metric

        hist = get_metric("ray_tpu_task_stage_seconds")
        if hist is not None and hasattr(hist, "quantiles_by"):
            for stage, qs in hist.quantiles_by("stage").items():
                for q, label in ((0.5, "p50"), (0.9, "p90"), (0.99, "p99")):
                    add(f"stage_{stage}_{label}", qs.get(q, 0.0))
        total_hist = get_metric("ray_tpu_task_total_seconds")
        if total_hist is not None and hasattr(total_hist, "quantiles_by"):
            merged = total_hist.quantiles_by("type")
            for ttype, qs in merged.items():
                for q, label in ((0.5, "p50"), (0.9, "p90"), (0.99, "p99")):
                    add(f"task_total_{ttype}_{label}", qs.get(q, 0.0))
        # 1.5) LLM serving series (serve.llm): scrape replica metric
        # snapshots into the local registry (no-op unless serve is
        # running and reachable from this process), then sample the
        # merged TTFT/TPOT quantiles and queue/occupancy gauges.
        try:
            from ray_tpu.serve.llm import metrics as llm_m

            llm_m.maybe_collect_local(timeout_s=2.0)
            for metric, label in ((llm_m.TTFT_NAME, "llm_ttft"),
                                  (llm_m.TPOT_NAME, "llm_tpot")):
                hist = get_metric(metric)
                if hist is not None and hasattr(hist, "quantiles_by"):
                    for dep, qs in hist.quantiles_by("deployment").items():
                        for q, ql in ((0.5, "p50"), (0.99, "p99")):
                            add(f"{label}_{dep}_{ql}", qs.get(q, 0.0))
            for metric, label in (
                    (llm_m.QUEUE_DEPTH_NAME, "llm_queue_depth"),
                    (llm_m.OCCUPANCY_NAME, "llm_batch_occupancy")):
                g = get_metric(metric)
                if g is not None:
                    for _, tags, v in g._samples():
                        add(f"{label}_{tags.get('replica', '')[:24]}", v)
        except Exception:  # noqa: BLE001 — serving stack not up
            pass
        # 1.55) device-plane performance: the input pipeline's
        # input_wait p50/p99 and HBM occupancy.
        hist = get_metric("ray_tpu_step_phase_seconds")
        if hist is not None and hasattr(hist, "quantiles_by"):
            for phase, qs in hist.quantiles_by("phase").items():
                for q, label in ((0.5, "p50"), (0.99, "p99")):
                    add(f"device_phase_{phase}_{label}", qs.get(q, 0.0))
        for metric, label in (("ray_tpu_hbm_bytes_in_use", "hbm_in_use"),
                              ("ray_tpu_hbm_bytes_peak", "hbm_peak")):
            g = get_metric(metric)
            if g is not None:
                for _, tags, v in g._samples():
                    add(f"{label}_{(tags.get('device') or '')[:24]}", v)
        # 1.6) overload protection (ISSUE 9): cluster-wide shed and
        # doomed-work totals from the GCS event manager's per-type
        # counts (covers every process, not just this one's registry),
        # plus this process's retry-budget fail-fast counter.
        try:
            stats = self._gcs.call("get_event_log_stats", {}, timeout=5)
            by_type = stats.get("by_type") or {}
            add("overload_shed_total", float(by_type.get("task.shed", 0)))
            add("overload_deadline_expired_total",
                float(by_type.get("task.deadline_expired", 0)))
        except Exception:  # noqa: BLE001 — GCS unreachable mid-sample
            pass
        budget_c = get_metric("ray_tpu_retry_budget_exhausted_total")
        if budget_c is not None:
            try:
                add("retry_budget_exhausted_total",
                    float(sum(v for _, v in budget_c._values.items())))
            except Exception:  # noqa: BLE001
                pass
        # 2) task throughput from GCS task events. Count FINISHED events
        # past a PER-JOB watermark over EVENT timestamps — a delta of the
        # windowed count would flatline to zero once the event store holds
        # more than the fetch window (exactly when the cluster is
        # busiest), a sample-wall-time cutoff would drop every event still
        # in an owner's ~1s flush buffer at fetch time, and one global
        # watermark would drop a lagging driver's events whenever another
        # driver's fresher flush landed first.
        try:
            events = self._gcs.call(
                "get_task_events", {"job_id": None, "limit": 10_000},
                timeout=5)
            wms = self._ts_event_watermarks
            fresh = 0
            batch_max: Dict[str, float] = {}
            for ev in events:
                if ev.get("state") != "FINISHED":
                    continue
                job, t = ev.get("job_id", ""), ev.get("time", 0)
                if t > wms.get(job, 0.0):
                    fresh += 1
                    if t > batch_max.get(job, 0.0):
                        batch_max[job] = t
            # marks advance only after the whole batch is counted — doing
            # it mid-loop would drop same-batch events older than a
            # fresher sibling
            wms.update(batch_max)
            self._ts_finished_cum += fresh
            add("tasks_finished_total", self._ts_finished_cum)
            # rate over the span since the last SUCCESSFUL fetch: using
            # the plain sample time would divide a whole GCS outage's
            # backlog by one 5s interval and render a phantom spike
            prev = self._ts_tp_prev_t
            if prev is not None and now > prev:
                add("task_throughput", fresh / (now - prev))
            self._ts_tp_prev_t = now
        except Exception:  # noqa: BLE001 — GCS restarting
            pass
        # 3) per-node raylet stats: store usage + lease queue depth
        try:
            nodes = self._gcs.call("get_all_node_info", {}, timeout=5)
        except Exception:  # noqa: BLE001
            nodes = []
        store_used = store_cap = 0
        active = queued = 0
        got_store = False
        for n in nodes:
            if not n.alive:
                continue
            try:
                st = self._raylets.get(n.raylet_address).call(
                    "get_node_stats", {}, timeout=3)
            except Exception:  # noqa: BLE001 — node mid-death
                continue
            active += st.get("active_leases", 0)
            queued += st.get("queued_leases", 0)
            store = st.get("store")
            if store:
                got_store = True
                store_used += store.get("used_bytes", 0)
                store_cap += store.get("capacity_bytes", 0)
        add("leases_active", active)
        add("leases_queued", queued)
        if got_store:
            add("store_used_bytes", store_used)
            add("store_capacity_bytes", store_cap)
        # 3b) memory plane: spill bytes + cluster ref/KV-block totals from
        # the cheap ({"refs": False}) get_cluster_memory fan-out. The same
        # report refreshes the ray_tpu_object_store_*/object_refs/
        # kv_blocks prometheus gauges served at /metrics.
        try:
            from ray_tpu._private import memory_obs

            mem = self._gcs.call(
                "get_cluster_memory",
                {"refs": False, "node_timeout_s": 4.0,
                 "worker_timeout_s": 2.0}, timeout=5)
            memory_obs.export_metrics(mem)
            spilled = 0
            refs = {"owned": 0, "borrowed": 0, "pinned": 0}
            kv = {"free": 0, "cached": 0, "active": 0}
            for node in (mem.get("nodes") or {}).values():
                if not isinstance(node, dict) or "error" in node:
                    continue
                spilled += (node.get("spill") or {}).get("bytes") or 0
            for _nid, _pid, rep in memory_obs.iter_worker_reports(mem):
                counts = rep.get("counts") or {}
                refs["owned"] += counts.get("num_owned", 0)
                refs["borrowed"] += counts.get("num_borrowed", 0)
                refs["pinned"] += counts.get("num_pinned", 0)
                for rpt in rep.get("kv") or ():
                    for state in kv:
                        kv[state] += int(rpt.get(f"{state}_blocks", 0))
            add("store_spilled_bytes", spilled)
            for kind, n in refs.items():
                add(f"object_refs_{kind}", n)
            if any(kv.values()):
                for state, n in kv.items():
                    add(f"kv_blocks_{state}", n)
        except Exception:  # noqa: BLE001 — GCS predating the RPC
            pass
        # 4) per-node CPU via the dashboard agents
        try:
            agents = self._agents()
        except Exception:  # noqa: BLE001
            agents = {}
        import urllib.request

        for node_id, url in agents.items():
            try:
                with urllib.request.urlopen(
                        f"{url}/api/local/stats", timeout=2) as resp:
                    st = json.loads(resp.read().decode())
                cpu = st.get("cpu_percent")
                if cpu is not None:
                    add(f"node_cpu_percent_{node_id[:8]}", cpu)
            except Exception:  # noqa: BLE001 — agent down
                continue

    def _timeseries(self) -> Dict[str, Any]:
        # Thin query over the GCS health store (ISSUE 20): the sampler
        # pushes every collected point there tagged src=dash, so the
        # series are cluster-wide state that survives dashboard restarts.
        # The local ring buffers stay as the fallback when the GCS (or a
        # GCS predating the RPC) can't answer. Sample on demand ONLY
        # while the rings are still empty — so the first page load has
        # data, without paying the multi-second cluster fan-out on an
        # HTTP request thread during an incident (nodes mid-death make
        # the fan-out slowest exactly when the user opens the dashboard
        # to look).
        with self._ts_lock:
            empty = not self._ts
        if empty:
            try:
                self._ts_sample()
            except Exception:  # noqa: BLE001
                logger.debug("on-demand sample failed", exc_info=True)
        now = time.time()
        series: Dict[str, list] = {}
        try:
            for row in self._gcs.call(
                    "query_metrics",
                    {"tags": {"src": "dash"}, "resolution": "raw",
                     "since": now - 3600.0, "limit_series": 500},
                    timeout=10):
                series[row["name"]] = [list(p) for p in row["points"]]
        except Exception:  # noqa: BLE001 — store-less GCS: local rings
            logger.debug("query_metrics failed; serving local rings",
                         exc_info=True)
            with self._ts_lock:
                series = {k: list(v) for k, v in self._ts.items()}
        # Per-series staleness from each point's collection stamp, plus
        # sampler health — so the SPA and the health scorecard can
        # distinguish a legitimately flat series from a dead sampler.
        stale_s = {
            name: round(now - pts[-1][0], 1)
            for name, pts in series.items() if pts}
        with self._ts_lock:
            last_success = self._ts_last_success
            failures = self._ts_fail_count
        return {
            "now": now,
            "sample_period_s": TS_SAMPLE_PERIOD_S,
            "series": series,
            "stale_s": stale_s,
            "stale_after_s": TS_SAMPLE_PERIOD_S * 3,
            "sampler": {
                "last_success": last_success,
                "age_s": (round(now - last_success, 1)
                          if last_success else None),
                "consecutive_failures": failures,
                "healthy": bool(
                    last_success
                    and now - last_success < TS_SAMPLE_PERIOD_S * 3),
            },
        }

    def _metrics_text(self) -> str:
        from ray_tpu.util.metrics import prometheus_text

        lines = [prometheus_text()]
        try:
            status = self._cluster_status()
            for k, v in status["resources_total"].items():
                name = k.replace(":", "_").replace(".", "_")
                lines.append(
                    f'ray_tpu_cluster_resource_total{{resource="{name}"}} {v}')
            for k, v in status["resources_available"].items():
                name = k.replace(":", "_").replace(".", "_")
                lines.append(
                    f'ray_tpu_cluster_resource_available{{resource="{name}"}}'
                    f' {v}')
            alive = sum(1 for n in status["nodes"].values() if n["alive"])
            from ray_tpu.util.metrics import get_metric

            if get_metric("ray_tpu_cluster_nodes_alive") is None:
                # embedded heads share a registry with the GCS, whose
                # metrics manager exports this as a real gauge — don't
                # emit the raw line twice
                lines.append(f"ray_tpu_cluster_nodes_alive {alive}")
        except Exception:  # noqa: BLE001 — GCS may be mid-restart
            pass
        return "\n".join(lines) + "\n"

    def _index_html(self) -> str:
        status = self._cluster_status()
        rows = "".join(
            f"<tr><td>{nid[:12]}</td>"
            f"<td>{'ALIVE' if n['alive'] else 'DEAD'}</td>"
            f"<td>{n['total']}</td></tr>"
            for nid, n in status["nodes"].items())
        return (
            "<html><head><title>ray_tpu dashboard</title></head><body>"
            "<h2>ray_tpu cluster</h2>"
            f"<p>GCS: {self.gcs_address}</p>"
            f"<p>Resources: {status['resources_available']} free of "
            f"{status['resources_total']}</p>"
            "<table border=1><tr><th>node</th><th>state</th>"
            f"<th>resources</th></tr>{rows}</table>"
            "<p>APIs: /api/cluster_status /api/nodes /api/actors /api/jobs "
            "/api/placement_groups /api/tasks /metrics</p>"
            "</body></html>")

    def stop(self) -> None:
        self._ts_stop.set()
        self._httpd.shutdown()
        self._httpd.server_close()
        self._raylets.close_all()
        self._gcs.close()
        self._lt.stop()
