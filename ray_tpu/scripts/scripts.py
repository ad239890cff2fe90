"""CLI: cluster lifecycle, jobs, state, debugging.

Reference: ray python/ray/scripts/scripts.py — `ray start:571`, `stop:1047`,
`status:1993`, `submit:1581`, `timeline:1879`, `memory:1944`,
`microbenchmark:1865`, plus `ray job ...` and `ray list ...`
(util/state/state_cli.py). Invoke as `python -m ray_tpu <cmd>`.

`start --head` runs a real head process (GCS + raylet + autoscaler-ready);
`start --address=H:P` joins a worker raylet — so multi-process /
multi-machine clusters work exactly like the reference's `ray start` flow.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

PIDFILE_DIR = "/tmp/rt_session"


def _pidfile(role: str) -> str:
    return os.path.join(PIDFILE_DIR, f"{role}-{os.getpid()}.pid")


def _write_pidfile(role: str, info: dict) -> None:
    os.makedirs(PIDFILE_DIR, exist_ok=True)
    with open(_pidfile(role), "w") as f:
        json.dump({"pid": os.getpid(), **info}, f)


def _all_pidfiles():
    if not os.path.isdir(PIDFILE_DIR):
        return []
    out = []
    for name in os.listdir(PIDFILE_DIR):
        if name.endswith(".pid"):
            try:
                with open(os.path.join(PIDFILE_DIR, name)) as f:
                    out.append((os.path.join(PIDFILE_DIR, name), json.load(f)))
            except (OSError, json.JSONDecodeError):
                continue
    return out


# ----------------------------------------------------------------- commands


def cmd_start(args) -> int:
    resources = json.loads(args.resources) if args.resources else {}
    if args.num_cpus is not None:
        resources["CPU"] = float(args.num_cpus)

    if args.head:
        from ray_tpu.gcs.server import GcsServer
        from ray_tpu.raylet.raylet import Raylet

        gcs = GcsServer()
        gcs_address = gcs.start(args.port or 0)
        raylet = Raylet(gcs_address=gcs_address,
                        resources=resources or None, is_head=True)
        raylet.start(0)
        dashboard = None
        agent = None
        if args.dashboard_port >= 0:
            try:
                from ray_tpu.dashboard import DashboardHead

                dashboard = DashboardHead(gcs_address,
                                          port=args.dashboard_port)
                print(f"Dashboard: {dashboard.url}")
            except OSError as e:
                print(f"dashboard disabled: {e}", file=sys.stderr)
            try:
                from ray_tpu.dashboard.agent import DashboardAgent

                agent = DashboardAgent(gcs_address, raylet.node_id.hex(),
                                       raylet.address)
            except Exception as e:  # noqa: BLE001 — node runs without one
                print(f"dashboard agent disabled: {e}", file=sys.stderr)
        _write_pidfile("head", {"address": gcs_address})
        print(f"Started head node.\n\n  GCS address: {gcs_address}\n\n"
              f"To add a worker node:\n"
              f"  python -m ray_tpu start --address={gcs_address}\n"
              f"To connect a driver:\n"
              f"  ray_tpu.init(address=\"{gcs_address}\")  # or "
              f"RT_ADDRESS={gcs_address}")
        if args.block:
            _block_forever()
            if agent is not None:
                agent.stop()
            if dashboard is not None:
                dashboard.stop()
            raylet.stop()
            gcs.stop()
        return 0

    if not args.address:
        print("either --head or --address=<gcs addr> is required",
              file=sys.stderr)
        return 1
    from ray_tpu.raylet.raylet import Raylet

    raylet = Raylet(gcs_address=args.address, resources=resources or None)
    raylet._exit_on_drain = True  # a drained worker process exits cleanly
    raylet.start(0)
    agent = None
    try:
        from ray_tpu.dashboard.agent import DashboardAgent

        agent = DashboardAgent(args.address, raylet.node_id.hex(),
                               raylet.address)
    except Exception as e:  # noqa: BLE001 — node runs without one
        print(f"dashboard agent disabled: {e}", file=sys.stderr)
    _write_pidfile("worker", {"address": args.address})
    print(f"Started worker node; joined {args.address}")
    if args.block:
        _block_forever()
        if agent is not None:
            agent.stop()
        raylet.stop()
    return 0


def _block_forever():
    stop = []
    signal.signal(signal.SIGTERM, lambda *a: stop.append(1))
    signal.signal(signal.SIGINT, lambda *a: stop.append(1))
    while not stop:
        time.sleep(0.25)


def cmd_stop(args) -> int:
    n = 0
    for path, info in _all_pidfiles():
        pid = info.get("pid")
        try:
            os.kill(pid, signal.SIGTERM)
            n += 1
        except (ProcessLookupError, TypeError):
            pass
        try:
            os.unlink(path)
        except OSError:
            pass
    print(f"Sent SIGTERM to {n} node process(es).")
    return 0


def cmd_up(args) -> int:
    """Launch/refresh a cluster from a YAML (reference: ray up,
    scripts.py:1282 -> commands.create_or_update_cluster:707)."""
    from ray_tpu.autoscaler.commands import create_or_update_cluster

    result = create_or_update_cluster(
        args.config, no_restart=args.no_restart,
        min_workers=args.min_workers)
    print(f"head: {result['head']}  address: {result['address']}")
    print(f"workers: {result['workers']}")
    if result["failed"]:
        print(f"FAILED workers: {result['failed']}", file=sys.stderr)
        return 1
    return 0


def cmd_down(args) -> int:
    from ray_tpu.autoscaler.commands import teardown_cluster

    teardown_cluster(args.config, workers_only=args.workers_only)
    print("cluster down.")
    return 0


def cmd_exec(args) -> int:
    from ray_tpu.autoscaler.commands import exec_cluster

    return exec_cluster(args.config, args.command)


def cmd_attach(args) -> int:
    from ray_tpu.autoscaler.commands import attach_cluster

    return attach_cluster(args.config)


def cmd_rsync_up(args) -> int:
    from ray_tpu.autoscaler.commands import rsync

    rsync(args.config, args.source, args.target, down=False)
    return 0


def cmd_rsync_down(args) -> int:
    from ray_tpu.autoscaler.commands import rsync

    rsync(args.config, args.source, args.target, down=True)
    return 0


def cmd_get_head_ip(args) -> int:
    from ray_tpu.autoscaler.commands import get_head_node_ip

    print(get_head_node_ip(args.config))
    return 0


def _connect(args):
    import ray_tpu

    addr = getattr(args, "address", None) or os.environ.get("RT_ADDRESS")
    ray_tpu.init(address=addr, ignore_reinit_error=True)
    return ray_tpu


def cmd_status(args) -> int:
    ray_tpu = _connect(args)
    from ray_tpu.util.state import cluster_event_stats, list_nodes

    nodes = list_nodes()
    total = ray_tpu.cluster_resources()
    avail = ray_tpu.available_resources()
    print(f"Nodes: {sum(1 for n in nodes if n['state'] == 'ALIVE')} alive / "
          f"{len(nodes)} total")
    for n in nodes:
        head = " (head)" if n.get("is_head_node") else ""
        print(f"  {n['node_id'][:12]} {n['state']}{head}  "
              f"{n['resources_total']}")
    print("\nResources:")
    for k in sorted(total):
        print(f"  {avail.get(k, 0):g}/{total[k]:g} {k}")
    # Memory plane: arena occupancy + spill per node and the cluster ref
    # totals, from the cheap ({"refs": False}) fan-out. Best-effort — an
    # old GCS without get_cluster_memory just omits the section.
    try:
        from ray_tpu._private import memory_obs
        from ray_tpu.util.state.api import get_cluster_memory

        cluster = get_cluster_memory(refs=False, node_timeout_s=10.0,
                                     worker_timeout_s=5.0)
        print("\nMemory:")
        for nid, node in sorted((cluster.get("nodes") or {}).items()):
            if not isinstance(node, dict) or "error" in node:
                print(f"  {nid[:12]} unreachable")
                continue
            store = node.get("store") or {}
            spill = node.get("spill") or {}
            line = (f"  {nid[:12]} arena "
                    f"{_fmt_bytes(store.get('used_bytes'))}/"
                    f"{_fmt_bytes(store.get('capacity_bytes'))}"
                    if store else f"  {nid[:12]} no shm store")
            if spill.get("objects"):
                line += (f", spilled {spill['objects']} obj "
                         f"({_fmt_bytes(spill.get('bytes', 0))})")
            print(line)
        totals = {"owned": 0, "borrowed": 0, "pinned": 0}
        for _n, _p, rep in memory_obs.iter_worker_reports(cluster):
            counts = rep.get("counts") or {}
            totals["owned"] += counts.get("num_owned", 0)
            totals["borrowed"] += counts.get("num_borrowed", 0)
            totals["pinned"] += counts.get("num_pinned", 0)
        print(f"  refs: {totals['owned']} owned, {totals['borrowed']} "
              f"borrowed, {totals['pinned']} pinned "
              "(`ray-tpu memory` for the full table)")
    except Exception as e:  # noqa: BLE001 — status degrades, not dies
        print(f"\nMemory: unavailable ({e})")
    # Event-pipeline health: silent drops anywhere in the cluster must be
    # visible here, not discovered during the next post-mortem.
    try:
        ev = cluster_event_stats()
    except Exception as e:  # noqa: BLE001 — status degrades, not dies
        print(f"\nEvent log: unavailable ({e})")
        return 0
    print(f"\nEvent log: {ev.get('total_events', 0)} events in the GCS "
          "buffer")
    for src, st in sorted((ev.get("sources") or {}).items()):
        print(f"  {src.split('#')[0]:<22} depth={st['depth']} "
              f"flush_lag={st['flush_lag_s']:.1f}s "
              f"dropped={st['dropped']} emitted={st['emitted']}")
    # Overload protection (ISSUE 9): shed-vs-doomed accounting straight
    # from the cluster event totals, split by layer from recent events.
    by_type = ev.get("by_type") or {}
    shed = int(by_type.get("task.shed", 0))
    expired = int(by_type.get("task.deadline_expired", 0))
    if shed or expired:
        from ray_tpu.util.state import list_cluster_events

        print(f"\nOverload protection: {shed} shed (typed pushback), "
              f"{expired} deadline-expired (doomed work dropped)")
        layers: dict = {}
        for etype in ("task.shed", "task.deadline_expired"):
            try:
                for e in list_cluster_events(etype=etype, limit=2000):
                    layer = (e.get("data") or {}).get("layer", "?")
                    layers.setdefault(etype, {}).setdefault(layer, 0)
                    layers[etype][layer] += 1
            except Exception:  # noqa: BLE001 — recent-window detail only
                pass
        for etype, counts in sorted(layers.items()):
            detail = ", ".join(f"{k}={v}" for k, v in sorted(counts.items()))
            print(f"  {etype:<24} recent: {detail}")
    # Serve control plane (ISSUE 12): incarnation + checkpoint freshness
    # + the last recovery's adopted-vs-restarted split — the numbers an
    # operator checks after a controller crash/restart.
    try:
        from ray_tpu.serve.context import CONTROLLER_NAME

        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        info = ray_tpu.get(controller.get_recovery_info.remote(),
                           timeout=5)
    except Exception:  # noqa: BLE001 — serve not running
        info = None
    if info:
        age = info.get("last_checkpoint_age_s")
        freshness = (f"last {age:.1f}s ago" if age is not None
                     else "no checkpoint yet")
        print(f"\nServe control plane: incarnation "
              f"{info.get('incarnation')}, "
              f"{info.get('checkpoints_written', 0)} checkpoint(s), "
              f"{freshness}")
        if info.get("recovered_at"):
            print(f"  last recovery: adopted "
                  f"{info.get('adopted_replicas', 0)} replica(s) + "
                  f"{info.get('adopted_proxies', 0)} proxy shard(s), "
                  f"{info.get('restarted_replicas', 0)} reconciled "
                  f"(restarted)")
    return 0


def cmd_submit(args) -> int:
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient(args.address)
    runtime_env = json.loads(args.runtime_env) if args.runtime_env else None
    entry = args.entrypoint
    if entry and entry[0] == "--":
        entry = entry[1:]
    import shlex

    sid = client.submit_job(
        entrypoint=" ".join(shlex.quote(a) for a in entry),
        runtime_env=runtime_env)
    print(f"Job submitted: {sid}")
    if args.no_wait:
        return 0
    for chunk in client.tail_job_logs(sid):
        sys.stdout.write(chunk)
        sys.stdout.flush()
    status = client.get_job_status(sid)
    print(f"\nJob {sid} finished: {status.value}")
    return 0 if status.value == "SUCCEEDED" else 1


def cmd_job(args) -> int:
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient(args.address)
    if args.job_cmd == "list":
        for d in client.list_jobs():
            print(f"{d.submission_id}  {d.status.value:10} {d.entrypoint}")
    elif args.job_cmd == "status":
        print(client.get_job_status(args.id).value)
    elif args.job_cmd == "logs":
        print(client.get_job_logs(args.id), end="")
    elif args.job_cmd == "stop":
        print("stopped" if client.stop_job(args.id) else "not running")
    return 0


def cmd_list(args) -> int:
    _connect(args)
    from ray_tpu.util import state as st

    fn = {
        "nodes": st.list_nodes, "actors": st.list_actors,
        "tasks": st.list_tasks, "jobs": st.list_jobs,
        "placement-groups": st.list_placement_groups,
        "objects": st.list_objects, "workers": st.list_workers,
    }[args.kind]
    rows = fn(limit=args.limit)
    print(json.dumps(rows, indent=2, default=str))
    return 0


def _fmt_bytes(n) -> str:
    if n is None:
        return "?"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(n) < 1024 or unit == "GiB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}GiB"


def _render_memory_table(rows, group_by=None, top: int = 0) -> str:
    """Pure row-list -> table renderer (unit-tested without a cluster).
    group_by None: one line per reference, largest first. group_by
    "owner"/"node": aggregate refs + bytes per group."""
    lines = []
    if group_by:
        key = {"owner": lambda r: r.get("owner_address") or r.get("holder")
               or "?",
               "node": lambda r: (r.get("node_id") or "?")[:12]}[group_by]
        groups = {}
        for r in rows:
            g = groups.setdefault(key(r), {"refs": 0, "bytes": 0,
                                           "pinned": 0, "borrowed": 0})
            g["refs"] += 1
            g["bytes"] += r.get("size_bytes") or 0
            g["pinned"] += 1 if r.get("pinned") else 0
            g["borrowed"] += 1 if r.get("kind") == "borrowed" else 0
        lines.append(f"{group_by.upper():<42} {'REFS':>6} {'BYTES':>10} "
                     f"{'PINNED':>7} {'BORROWED':>9}")
        ordered = sorted(groups.items(), key=lambda kv: -kv[1]["bytes"])
        if top:
            ordered = ordered[:top]
        for name, g in ordered:
            lines.append(f"{str(name):<42} {g['refs']:>6} "
                         f"{_fmt_bytes(g['bytes']):>10} {g['pinned']:>7} "
                         f"{g['borrowed']:>9}")
        return "\n".join(lines)
    lines.append(f"{'OBJECT_ID':<14} {'KIND':<9} {'SIZE':>10} {'AGE':>8} "
                 f"{'PIN':>4} {'LREF':>5} {'BRW':>4} {'NODE':<13} "
                 f"{'HOLDER':<21} OWNER")
    ordered = sorted(rows, key=lambda r: -(r.get("size_bytes") or 0))
    if top:
        ordered = ordered[:top]
    for r in ordered:
        age = r.get("age_s")
        borrowers = r.get("borrowers")
        n_brw = len(borrowers) if isinstance(borrowers, (list, tuple)) \
            else (borrowers or 0)
        lines.append(
            f"{r.get('object_id', '?')[:12]:<14} "
            f"{r.get('kind', '?'):<9} "
            f"{_fmt_bytes(r.get('size_bytes')):>10} "
            f"{(f'{age:.0f}s' if age is not None else '?'):>8} "
            f"{('Y' if r.get('pinned') else '-'):>4} "
            f"{r.get('local_refs', 0):>5} {n_brw:>4} "
            f"{(r.get('node_id') or '?')[:12]:<13} "
            f"{str(r.get('holder') or '?'):<21} "
            f"{r.get('owner_address') or '-'}")
    return "\n".join(lines)


def cmd_memory(args) -> int:
    """Cluster-wide memory report: per-node arena/spill occupancy, every
    worker's reference table (sizes, ages, pins, borrowers), KV-block
    pools, and an optional leak sweep. --local keeps the old driver-only
    snapshot (no fan-out)."""
    ray_tpu = _connect(args)
    cw = ray_tpu._raylet.get_core_worker()
    if getattr(args, "local", False):
        stats = {"memory_store_objects": cw.memory_store.size(),
                 "memory_store_bytes": cw.memory_store.total_bytes()}
        if cw.plasma is not None:
            n, used, cap = cw.plasma._client.stats()
            stats["shm_store"] = {"objects": n, "used_bytes": used,
                                  "capacity_bytes": cap}
        print(json.dumps(stats, indent=2))
        return 0

    from ray_tpu._private import memory_obs
    from ray_tpu.util.state.api import get_cluster_memory

    include_refs = not args.stats_only
    cluster = get_cluster_memory(refs=include_refs,
                                 node_timeout_s=args.timeout,
                                 worker_timeout_s=args.timeout / 2)
    verdict = None
    if args.leaks:
        verdict = memory_obs.sweep_and_emit(
            cluster, max_age_s=args.max_age,
            min_orphan_age_s=args.min_orphan_age)
    if args.json:
        out = dict(cluster)
        if verdict is not None:
            out["leak_sweep"] = verdict
        print(json.dumps(out, indent=2, default=str))
        return 1 if verdict and verdict["suspects"] else 0

    for nid, node in sorted((cluster.get("nodes") or {}).items()):
        if not isinstance(node, dict) or "error" in node:
            err = node.get("error") if isinstance(node, dict) else node
            print(f"node {nid[:12]}: UNREACHABLE ({err})", file=sys.stderr)
            continue
        store = node.get("store") or {}
        spill = node.get("spill") or {}
        workers = node.get("workers") or {}
        if store:
            frag = store.get("fragmentation") or 0.0
            print(f"node {nid[:12]}: arena "
                  f"{_fmt_bytes(store.get('used_bytes'))}/"
                  f"{_fmt_bytes(store.get('capacity_bytes'))} "
                  f"({store.get('objects', 0)} objects, "
                  f"frag {frag:.2f}, largest hole "
                  f"{_fmt_bytes(store.get('largest_free_bytes'))})")
        else:
            print(f"node {nid[:12]}: no shm store")
        if spill:
            pend = len(spill.get("pending_uris") or ())
            print(f"  spill: {spill.get('objects', 0)} objects, "
                  f"{_fmt_bytes(spill.get('bytes', 0))}"
                  + (f", {pend} restore(s) pending" if pend else ""))
        n_err = sum(1 for w in workers.values()
                    if isinstance(w, dict) and "error" in w)
        print(f"  workers reporting: {len(workers) - n_err}/{len(workers)}")
        for pid, w in sorted(workers.items()):
            if isinstance(w, dict) and "error" in w:
                print(f"    pid {pid}: {w['error']}", file=sys.stderr)
    kv_reports = [kv for _n, _p, rep in memory_obs.iter_worker_reports(cluster)
                  for kv in rep.get("kv") or ()]
    if kv_reports:
        free = sum(k.get("free_blocks", 0) for k in kv_reports)
        cached = sum(k.get("cached_blocks", 0) for k in kv_reports)
        active = sum(k.get("active_blocks", 0) for k in kv_reports)
        hits = sum((k.get("prefix_stats") or {}).get("hit_tokens", 0)
                   for k in kv_reports)
        saved = sum((k.get("prefix_stats") or {}).get("bytes_saved", 0)
                    for k in kv_reports)
        print(f"\nKV blocks ({len(kv_reports)} engine(s)): {active} active, "
              f"{cached} cached, {free} free; prefix cache: {hits} hit "
              f"tokens, {_fmt_bytes(saved)} saved")

    if include_refs:
        rows = memory_obs.flatten_refs(cluster)
        print(f"\n{len(rows)} reference(s) cluster-wide:")
        print(_render_memory_table(rows, group_by=args.group_by,
                                   top=args.top))

    if verdict is not None:
        suspects = verdict["suspects"]
        print(f"\nLeak sweep: {len(suspects)} suspect(s)")
        for s in suspects:
            age = s.get("age_s")
            extra = "" if age is None else f" age {age:.0f}s"
            if s.get("holder"):
                extra += f" holder {s['holder']}"
            if s.get("owner"):
                extra += f" owner {s['owner']}"
            print(f"  {s['kind']:<14} {s['object_id'][:12]} "
                  f"{_fmt_bytes(s.get('size_bytes'))}{extra}")
        for p in verdict["pressure"]:
            print(f"  PRESSURE node {p['node_id'][:12]}: "
                  f"{_fmt_bytes(p['used_bytes'])}/"
                  f"{_fmt_bytes(p['capacity_bytes'])} "
                  f"({p['frac']:.0%})")
        return 1 if suspects else 0
    return 0


def cmd_timeline(args) -> int:
    """Dump task events as a chrome://tracing file (reference: ray timeline
    -> chrome_tracing_dump, _private/state.py:434)."""
    _connect(args)
    from ray_tpu.util.state.api import task_timeline_events

    trace = task_timeline_events(limit=args.limit, task_id=args.task_id)
    out = args.output or "timeline.json"
    with open(out, "w") as f:
        json.dump(trace, f)
    print(f"Wrote {len(trace)} events to {out} "
          f"(open in chrome://tracing or perfetto.dev)")
    return 0


def cmd_latency(args) -> int:
    """Per-stage latency breakdown of recent tasks (submit/queue/rpc/
    dispatch/execute/reply) from the GCS task-event stream — the numbers
    the control-plane perf work optimizes against."""
    _connect(args)
    from ray_tpu._private import latency
    from ray_tpu.util.state.api import list_tasks

    events = list_tasks(limit=100_000, raw_events=True)
    evs = [e for e in events if e.get("stages")]
    evs.sort(key=lambda e: e.get("time", 0))
    evs = evs[-args.n:]
    if not evs:
        print("no task breakdowns recorded yet (run some tasks first; "
              "breakdowns ride the terminal task events)")
        return 0
    rows = [{"name": e.get("name"), "type": e.get("type"),
             "task_id": e.get("task_id"), "stages": e["stages"]}
            for e in evs]
    print(f"stage breakdown of the last {len(rows)} finished tasks "
          "(milliseconds):")
    print(latency.format_breakdowns(rows))
    return 0


def cmd_events(args) -> int:
    """`ray-tpu events`: the cluster-wide structured lifecycle event log
    (FSM transitions, retry/lease/recovery decisions, spills, chaos
    firings) with filters — the first stop when a distributed failure
    needs a WHO-did-WHAT-WHEN answer on a live cluster. Per-task causal
    timelines (retries and lineage reconstruction included): --task-id
    --causal."""
    _connect(args)
    from ray_tpu._private.event_log import format_events
    from ray_tpu.util.state import list_cluster_events, task_causal_timeline

    if args.causal:
        if not args.task_id:
            print("--causal requires --task-id", file=sys.stderr)
            return 1
        events = task_causal_timeline(args.task_id)
    else:
        events = list_cluster_events(
            limit=args.limit, etype=args.type, task_id=args.task_id,
            actor_id=args.actor_id, node_id=args.node_id)
        events = sorted(events, key=lambda e: (e.get("time", 0),
                                               e.get("pid") or 0,
                                               e.get("seq") or 0))
    if args.json:
        print(json.dumps(events, indent=2, default=str))
        return 0
    if not events:
        print("no matching events (lifecycle events flush within ~1s of "
              "emission; check filters)")
        return 0
    print(format_events(events))
    return 0


def cmd_trace(args) -> int:
    """`ray-tpu trace <trace_id>` — the cross-process span tree of one
    distributed request (proxy -> router -> owner -> raylet -> worker ->
    engine), with per-span durations and the lifecycle events stamped
    with the same trace id. `--list` shows recent sampled/force-kept
    traces; `--chrome FILE` exports a merged chrome trace whose flow
    events link the process lanes."""
    _connect(args)
    from ray_tpu._private import tracing as _tracing
    from ray_tpu._private.event_log import format_events
    from ray_tpu.util.state import get_trace, list_traces, trace_events

    # local spans flush on a 1s cadence; give this process's tail a push
    _tracing.flush_spans(timeout=1.0)
    if args.list or not args.trace_id:
        rows = list_traces(limit=args.limit)
        if args.json:
            print(json.dumps(rows, indent=2, default=str))
            return 0
        if not rows:
            print("no stored traces (sampled or force-kept) yet — pass a "
                  "sampled traceparent, raise trace_sample_rate, or look "
                  "up a recent trace id from a response's X-Trace-Id "
                  "header directly")
            return 0
        for t in rows:
            ts = time.strftime("%H:%M:%S", time.localtime(t["start"]))
            forced = (f" forced={t['forced_reason']}"
                      if t.get("forced_reason") else "")
            print(f"{t['trace_id']}  {ts}  {t['duration_s'] * 1e3:8.2f}ms  "
                  f"{t['spans']:>3} span(s)  {len(t['procs'])} proc(s)  "
                  f"root={t.get('root')}{forced}")
        return 0
    reply = get_trace(args.trace_id)
    spans = reply.get("spans") or []
    if args.json:
        print(json.dumps(reply, indent=2, default=str))
        return 0
    if not spans:
        print(f"no spans stored for trace {args.trace_id} (unsampled "
              "traces age out of the provisional ring unless force-kept; "
              "spans flush within ~1s of recording)")
        return 1
    if args.chrome:
        trace = _tracing.trace_chrome(spans)
        with open(args.chrome, "w") as f:
            json.dump(trace, f)
        print(f"Wrote {len(trace)} chrome-trace events to {args.chrome} "
              f"(open in chrome://tracing or perfetto.dev)")
        return 0
    if reply.get("forced"):
        print(f"force-kept: {reply.get('forced_reason')}")
    print(_tracing.format_trace(spans))
    events = trace_events(args.trace_id)
    if events:
        print(f"\nlifecycle events carrying this trace id ({len(events)}; "
              "cross-ref: ray-tpu debug postmortem --trace-id):")
        print(format_events(events))
    return 0


def cmd_serve(args) -> int:
    """serve deploy/status/shutdown (reference: serve/scripts.py CLI)."""
    _connect(args)
    from ray_tpu import serve

    if args.serve_cmd == "deploy":
        if not args.config:
            print("serve deploy requires a JSON config path", file=sys.stderr)
            return 1
        from ray_tpu.serve.schema import ServeDeploySchema, deploy_config

        config = ServeDeploySchema.parse_file(args.config)
        handles = deploy_config(config)
        print(f"Deployed {len(handles)} application(s): "
              f"{', '.join(handles)}")
    elif args.serve_cmd == "status":
        print(json.dumps(serve.status(), indent=2, default=str))
    elif args.serve_cmd == "shutdown":
        serve.shutdown()
        print("Serve shut down.")
    return 0


def cmd_llm(args) -> int:
    """`ray-tpu llm status`: live serving health of every serve.llm app —
    per-replica queue depth / batch occupancy / preemptions plus the
    cluster-merged TTFT & TPOT percentiles (the numbers that say whether
    the service is keeping up, before clients notice)."""
    _connect(args)
    import ray_tpu
    from ray_tpu.serve import context as serve_ctx
    from ray_tpu.serve.llm import metrics as llm_metrics

    if args.llm_cmd != "status":
        print(f"unknown llm subcommand {args.llm_cmd!r}", file=sys.stderr)
        return 1
    try:
        controller = serve_ctx.get_controller()
    except RuntimeError:
        print("Serve is not running.")
        return 1
    apps = llm_metrics.find_llm_apps(controller)
    if not apps:
        print("no serve.llm applications deployed "
              "(see serve.llm.build_llm_app)")
        return 0
    scraped = llm_metrics.collect_llm_metrics()
    out = {"replicas_scraped": scraped, "applications": {}}
    for app, names in apps.items():
        info = {"engine_deployment": names["engine"],
                "deployment_status": ray_tpu.get(
                    controller.get_deployment_status.remote(
                        app, names["engine"])),
                "replicas": [], "router": None}
        for h in ray_tpu.get(controller.get_replica_handles.remote(
                app, names["engine"])):
            try:
                info["replicas"].append(ray_tpu.get(
                    h.handle_request.remote("get_stats", (), {}),
                    timeout=10))
            except Exception as e:  # noqa: BLE001 — replica mid-restart
                info["replicas"].append({"error": str(e)[:200]})
        for h in ray_tpu.get(controller.get_replica_handles.remote(
                app, names["ingress"])):
            try:
                info["router"] = ray_tpu.get(
                    h.handle_request.remote("get_router_stats", (), {}),
                    timeout=10)
                break
            except Exception as e:  # noqa: BLE001
                info["router"] = {"error": str(e)[:200]}
        out["applications"][app] = info
    out["metrics"] = llm_metrics.serving_summary()
    if args.json:
        print(json.dumps(out, indent=2, default=str))
        return 0
    for app, info in out["applications"].items():
        st = info["deployment_status"]
        print(f"app {app!r}: engine={info['engine_deployment']} "
              f"[{st.get('status')}] replicas="
              f"{st.get('replicas')}/{st.get('target_replicas')}")
        for rs in info["replicas"]:
            if "error" in rs:
                print(f"  replica: unreachable ({rs['error']})")
                continue
            eng = rs.get("engine", {})
            print(f"  replica: queue={rs.get('queue_depth')} "
                  f"in-flight={rs.get('outstanding_requests')} "
                  f"done={rs.get('finished_requests')} "
                  f"slots={eng.get('active_slots')}/{eng.get('max_batch')} "
                  f"preemptions={eng.get('preemptions', 0)}")
            pc = eng.get("prefix_cache")
            if pc and pc.get("enabled"):
                print(f"    prefix-cache: hits={pc.get('hit_requests', 0)} "
                      f"misses={pc.get('miss_requests', 0)} "
                      f"hit_tokens={pc.get('hit_tokens', 0)} "
                      f"evictions={pc.get('evictions', 0)} "
                      f"cached_blocks={pc.get('cached_blocks', 0)} "
                      f"bytes_saved={pc.get('bytes_saved', 0)}")
        router = info.get("router") or {}
        if router and "error" not in router:
            print(f"  router: assigned={router.get('assigned_total')} "
                  f"outstanding_tokens={router.get('outstanding_tokens')} "
                  f"shed={router.get('shed_total')} "
                  f"sessions={router.get('sessions')}")
    m = out["metrics"]
    for name, label in (("ttft_s", "TTFT"), ("tpot_s", "TPOT")):
        for dep, qs in (m.get(name) or {}).items():
            print(f"{label} [{dep}]: "
                  f"p50={qs.get(0.5, 0) * 1e3:.1f}ms "
                  f"p99={qs.get(0.99, 0) * 1e3:.1f}ms "
                  f"(n={qs.get('count', 0)})")
    print(f"tokens_generated={m.get('tokens_generated', 0):.0f} "
          f"preemptions={m.get('preemptions', 0):.0f} "
          f"shed={m.get('requests_shed', 0):.0f} "
          f"requests={m.get('requests', {})}")
    pc = m.get("prefix_cache")
    if pc:
        print(f"prefix_cache: hits={pc.get('hit_requests', 0):.0f} "
              f"misses={pc.get('miss_requests', 0):.0f} "
              f"hit_tokens={pc.get('hit_tokens', 0):.0f} "
              f"evictions={pc.get('evictions', 0):.0f} "
              f"bytes_saved={pc.get('bytes_saved', 0):.0f}")
    return 0


def cmd_logs(args) -> int:
    """Tail worker logs across the cluster (reference: `ray logs` /
    dashboard log routes; data comes from each raylet's
    tail_worker_logs RPC over the live cluster)."""
    ray_tpu = _connect(args)
    from ray_tpu._raylet import get_core_worker
    from ray_tpu.util.state.api import collect_worker_logs

    cw = get_core_worker()
    result = collect_worker_logs(
        cw._gcs.call("get_all_node_info", {}),
        lambda addr, payload: cw._peers.get(addr).call(
            "tail_worker_logs", payload, timeout=30),
        node_id=args.node_id, pid=args.pid, lines=args.lines)
    shown = 0
    for nid, workers in sorted(result.items()):
        if "error" in workers:
            print(f"node {nid[:8]}: unreachable ({workers['error']})")
            continue
        for pid, info in sorted(workers.items()):
            if not info["lines"] and not args.all:
                continue
            print(f"--- node {nid[:8]} pid={pid} "
                  f"state={info['state']} ({info['path']})")
            for line in info["lines"]:
                print(f"    {line}")
            shown += 1
    if shown == 0:
        print("no worker logs found")
    ray_tpu.shutdown()
    return 0


def cmd_metrics(args) -> int:
    """`metrics grafana-dashboard`: write importable Grafana JSON for the
    cluster's Prometheus series (reference: `ray metrics` + the dashboard's
    grafana_dashboard_factory.py)."""
    if args.metrics_cmd == "grafana-dashboard":
        from ray_tpu.dashboard.grafana import write_grafana_dashboard

        out = args.output or "ray_tpu_grafana_dashboard.json"
        write_grafana_dashboard(out)
        print(f"wrote {out} (import in Grafana with a Prometheus data "
              "source scraping the dashboard /metrics endpoint)")
        return 0
    if args.metrics_cmd == "launch-prometheus":
        # Reference: `ray metrics launch-prometheus` (scripts.py:2539)
        # downloads + starts Prometheus against generated scrape configs.
        # Zero-egress here: generate the config, then start a locally
        # installed `prometheus` binary if one exists.
        import shutil
        import subprocess

        target = args.scrape_target or "127.0.0.1:8265"
        out = args.output or "ray_tpu_prometheus.yml"
        with open(out, "w") as f:
            f.write(
                "global:\n"
                "  scrape_interval: 10s\n"
                "scrape_configs:\n"
                "  - job_name: ray_tpu\n"
                "    metrics_path: /metrics\n"
                "    static_configs:\n"
                f"      - targets: ['{target}']\n"
            )
        print(f"wrote {out} (scraping {target})")
        binary = shutil.which("prometheus")
        if binary is None:
            print("no `prometheus` binary on PATH; install it and run:\n"
                  f"  prometheus --config.file={out}")
            return 0
        proc = subprocess.Popen([binary, f"--config.file={out}"])
        print(f"started prometheus (pid {proc.pid})")
        return 0
    print(f"unknown metrics subcommand {args.metrics_cmd!r}")
    return 1


def cmd_kill_random_node(args) -> int:
    """Chaos helper (reference: `ray kill-random-node`, scripts.py:1384):
    ungracefully kill one random non-head node's raylet process so
    failure-recovery paths can be exercised on a live cluster."""
    import random

    ray_tpu = _connect(args)
    from ray_tpu._raylet import get_core_worker

    cw = get_core_worker()
    nodes = [n for n in cw._gcs.call("get_all_node_info", {})
             if n.alive and not n.is_head]
    if not nodes:
        print("no non-head nodes to kill")
        ray_tpu.shutdown()
        return 1
    victim = random.choice(nodes)
    if not args.yes:
        print(f"would kill node {victim.node_id.hex()[:12]} at "
              f"{victim.raylet_address}; pass --yes to proceed")
        ray_tpu.shutdown()
        return 1
    try:
        # a successful send never raises (the raylet delays its os._exit
        # past the reply), so any exception here is genuine non-delivery
        cw._peers.get(victim.raylet_address).send("die", {})
    except Exception as e:  # noqa: BLE001
        print(f"FAILED to reach node {victim.node_id.hex()[:12]} at "
              f"{victim.raylet_address}: {e}")
        ray_tpu.shutdown()
        return 1
    print(f"killed node {victim.node_id.hex()[:12]} "
          f"({victim.raylet_address}); the GCS will notice via missed "
          "heartbeats")
    ray_tpu.shutdown()
    return 0


def cmd_chaos(args) -> int:
    """Message-level chaos control (`ray-tpu chaos start|stop|status`):
    installs a deterministic, seeded fault-injection plan on the GCS and
    every alive raylet (see ray_tpu.chaos / _private/fault_injection.py).
    Builds on `kill-random-node` — that kills processes, this drops,
    delays, duplicates, errors, or disconnects individual RPCs."""
    import json as _json

    from ray_tpu import chaos

    gcs_addr = args.address or os.environ.get("RT_ADDRESS")
    if not gcs_addr:
        print("--address (or RT_ADDRESS) is required", file=sys.stderr)
        return 1
    if args.chaos_cmd == "start":
        if args.plan:
            with open(args.plan) as f:
                plan_json = f.read()
            if args.seed is not None:
                doc = _json.loads(plan_json)
                doc["seed"] = args.seed
                plan_json = _json.dumps(doc)
        elif args.kill_point:
            # quick single-rule plan without a file: kill a matching
            # process at a lifecycle point (before_execute / after_reply /
            # mid_stream), e.g. --kill-point mid_stream --label worker
            plan_json = chaos.ChaosPlan(
                seed=args.seed or 0,
                rules=[chaos.ChaosRule(
                    action="kill", site=args.kill_point,
                    method=args.method, label=args.label,
                    p=args.p, after=args.after, times=args.times or 1)],
            ).to_json()
        else:
            print("chaos start needs --plan FILE or --kill-point SITE",
                  file=sys.stderr)
            return 1
        if not args.yes:
            print("this will inject faults into live cluster traffic; "
                  "pass --yes to proceed")
            return 1
        # Cluster install covers the GCS + raylet PROCESSES only; worker
        # (and driver) processes arm from RAY_TPU_CHAOS at their own
        # start. A rule addressed at those endpoints would report
        # "installed" yet never fire — say so instead of silently no-oping.
        plan_obj = chaos.ChaosPlan.from_json(plan_json)
        from fnmatch import fnmatchcase

        def _cluster_reachable(r):
            # A cluster install arms GCS + raylet processes at the three
            # transport sites; a rule reaches them only if BOTH its label
            # and site globs can match there. Default "*" globs match, so
            # only rules pinned to worker/driver (or mid_stream-only)
            # warn.
            return (any(fnmatchcase(lb, r.label) for lb in ("gcs", "raylet"))
                    and any(fnmatchcase(s, r.site)
                            for s in (chaos.SITE_CLIENT_REQUEST,
                                      chaos.SITE_BEFORE_EXECUTE,
                                      chaos.SITE_AFTER_REPLY)))

        unreachable = [r for r in plan_obj.rules if not _cluster_reachable(r)]
        if unreachable:
            print(f"WARNING: {len(unreachable)} rule(s) target worker/"
                  "driver endpoints (label worker/driver or site "
                  "mid_stream). `chaos start` installs on GCS + raylet "
                  "processes only — those rules fire there ONLY if the "
                  "label glob also matches gcs/raylet. To arm workers, "
                  f"export {chaos.ENV_VAR} before starting nodes (workers "
                  "inherit it at spawn).", file=sys.stderr)
        reply = chaos.start_cluster(plan_json, gcs_addr)
        print(_json.dumps(reply, indent=2, default=str))
        return 0 if reply.get("status") == "installed" else 1
    if args.chaos_cmd == "stop":
        reply = chaos.stop_cluster(gcs_addr)
        print(_json.dumps(reply, indent=2, default=str))
        return 0
    reply = chaos.cluster_status(gcs_addr)
    # Per-rule match counts from the cluster EVENT LOG: the audit trail of
    # what actually fired, durable past `chaos stop` and inclusive of
    # worker-process firings the plan objects on GCS/raylets never saw.
    try:
        reply["injection_history"] = chaos.injection_history(gcs_addr)
    except Exception as e:  # noqa: BLE001 — history is additive info
        reply["injection_history"] = {"error": str(e)}
    print(_json.dumps(reply, indent=2, default=str))
    return 0


def cmd_client_server(args) -> int:
    """Run the client proxy (reference: `ray start --ray-client-server-port`
    / util/client/server): remote drivers connect with
    ray_tpu.init("client://host:port", token=...)."""
    from ray_tpu.util.client import ClientProxyServer

    gcs = args.address or os.environ.get("RT_ADDRESS")
    if not gcs:
        print("--address (or RT_ADDRESS) is required")
        return 1
    token = args.token or os.environ.get("RT_CLIENT_TOKEN")
    server = ClientProxyServer(gcs, host=args.host, token=token)
    addr = server.start(args.port)
    print(f"client proxy serving at client://{addr}"
          + (" (token required)" if token else " (NO token — open access)"))
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        server.stop()
    return 0


def _task_stage_spans(events) -> list:
    """PR 1 task-stage breakdowns (terminal task events carrying 'stages')
    rendered as span dicts — the six stages laid back-to-back ending at
    the event instant, one lane — for the `ray-tpu profile --device`
    chrome merge against device-phase lanes."""
    from ray_tpu._private.latency import STAGES

    spans = []
    for i, e in enumerate(events):
        stages = e.get("stages") or {}
        total = sum(stages.get(s, 0.0) or 0.0 for s in STAGES)
        t_end = e.get("time", 0.0)
        root = f"task-{i}"
        spans.append({
            "span_id": root, "parent_id": None, "trace_id": None,
            "name": str(e.get("name") or e.get("task_id", "?")),
            "proc": "tasks", "thread": "task-stages",
            "start": t_end - total, "end": t_end,
            "attrs": {"task_id": e.get("task_id"),
                      "type": e.get("type")},
        })
        t = t_end - total
        for s in STAGES:
            dur = stages.get(s, 0.0) or 0.0
            if dur <= 0:
                continue
            spans.append({
                "span_id": f"{root}-{s}", "parent_id": root,
                "trace_id": None, "name": f"{e.get('name', '?')}:{s}",
                "proc": "tasks", "thread": "task-stages",
                "start": t, "end": t + dur, "attrs": {"stage": s},
            })
            t += dur
    return spans


def _cmd_profile_device(args) -> int:
    """`ray-tpu profile --device` (ISSUE 15): fan per-worker device-plane
    phase reports out through every raylet, merge them with the driver's
    own profilers, print the phase-attribution table, and optionally
    export ONE chrome trace whose lanes carry device phases next to the
    PR 1 task-stage spans."""
    import json as _json

    ray_tpu = _connect(args)
    from ray_tpu._private import device_profiler
    from ray_tpu._raylet import get_core_worker

    reports = []  # (proc label, per-profiler report)
    local = device_profiler.snapshot_all(recent=args.recent)
    for _name, rep in sorted(local.get("profilers", {}).items()):
        reports.append((f"driver:{local.get('pid', '?')}", rep))
    cw = get_core_worker()
    for n in cw._gcs.call("get_all_node_info", {}):
        if not n.alive:
            continue
        try:
            r = cw._peers.get(n.raylet_address).call(
                "profile_worker", {"kind": "device",
                                   "recent": args.recent}, timeout=60)
        except Exception as e:  # noqa: BLE001 — keep trying other nodes
            print(f"node {n.node_id.hex()[:8]}: unreachable ({e})",
                  file=sys.stderr)
            continue
        for pid, snap in sorted((r.get("workers") or {}).items()):
            if not isinstance(snap, dict) or "error" in snap:
                continue
            for _name, rep in sorted((snap.get("profilers") or {}).items()):
                reports.append((f"worker:{pid}", rep))
    if args.json:
        print(_json.dumps([{"proc": p, **r} for p, r in reports],
                          indent=2, default=str))
    elif not reports:
        print("no device-step profilers registered anywhere (a profiler "
              "appears with the first profiled train step / decode wave; "
              "the paged engine registers one)")
    else:
        hdr = (f"{'proc':<16} {'profiler':<12} {'steps':>6} "
               f"{'input_wait':>10} {'h2d':>7} {'compile_s':>9} "
               f"{'device':>7} {'reply':>7}")
        print(hdr)
        print("-" * len(hdr))
        for proc, rep in reports:
            print(f"{proc:<16} {rep.get('profiler', '?'):<12} "
                  f"{rep.get('steps', 0):>6} "
                  f"{rep.get('input_wait_frac', 0.0):>10.3f} "
                  f"{rep.get('h2d_frac', 0.0):>7.3f} "
                  f"{rep.get('compile_s', 0.0):>9.3f} "
                  f"{rep.get('device_execute_frac', 0.0):>7.3f} "
                  f"{rep.get('reply_frac', 0.0):>7.3f}")
    if args.chrome:
        from ray_tpu._private import tracing as _tracing
        from ray_tpu.util.state.api import list_tasks

        spans = []
        for proc, rep in reports:
            spans.extend(device_profiler.steps_to_spans(rep, proc))
        try:
            events = [e for e in list_tasks(limit=100_000, raw_events=True)
                      if e.get("stages")]
        except Exception:  # noqa: BLE001 — GCS task events unavailable
            events = []
        spans.extend(_task_stage_spans(events))
        trace = _tracing.trace_chrome(spans)
        with open(args.chrome, "w") as f:
            _json.dump(trace, f)
        print(f"Wrote {len(trace)} chrome-trace events to {args.chrome} "
              f"(device phases + task stages; open in chrome://tracing "
              f"or perfetto.dev)")
    ray_tpu.shutdown()
    return 0


def cmd_profile(args) -> int:
    """Live CPU flamegraph / heap snapshot of a worker (reference: the
    dashboard's py-spy and memray endpoints, profile_manager.py:83/:192),
    or — with --device — the cluster-wide device-plane phase report."""
    import json as _json

    if getattr(args, "device", False):
        return _cmd_profile_device(args)
    if args.pid is None:
        print("--pid is required for --cpu/--memory profiles "
              "(--device fans out to every worker)", file=sys.stderr)
        return 1
    ray_tpu = _connect(args)
    from ray_tpu._raylet import get_core_worker
    from ray_tpu.util.profiling import folded_to_text

    cw = get_core_worker()
    payload = {"pid": args.pid,
               "kind": "memory" if (args.memory or getattr(
                   args, "memory_stop", False)) else "cpu",
               "duration_s": args.duration, "top": args.top,
               "stop": bool(getattr(args, "memory_stop", False))}
    reply = None
    try:
        for n in cw._gcs.call("get_all_node_info", {}):
            if not n.alive:
                continue
            try:
                r = cw._peers.get(n.raylet_address).call(
                    "profile_worker", payload, timeout=args.duration + 60)
            except Exception as e:  # noqa: BLE001 — keep trying other nodes
                print(f"node {n.node_id.hex()[:8]}: unreachable ({e})",
                      file=sys.stderr)
                continue
            if "error" not in r:
                reply = r
                break
    finally:
        if reply is None:
            ray_tpu.shutdown()
    if reply is None:
        print(f"no live worker with pid {args.pid}")
        return 1
    if args.memory or getattr(args, "memory_stop", False):
        if getattr(args, "folded", False):
            # flamegraph.pl-compatible heap stacks (size bytes as counts)
            print(folded_to_text(reply, top=args.top))
            print(f"# traced {reply.get('traced_current_bytes', 0)} bytes "
                  f"(peak {reply.get('traced_peak_bytes', 0)})",
                  file=sys.stderr)
        else:
            print(_json.dumps(reply, indent=2))
    else:
        # flamegraph.pl / speedscope-compatible folded stacks
        print(folded_to_text(reply, top=args.top))
        print(f"# {reply['samples']} samples over {reply['duration_s']}s",
              file=sys.stderr)
    ray_tpu.shutdown()
    return 0


def cmd_stack(args) -> int:
    """Dump python stacks of this node's worker processes (reference: ray
    stack — scripts.py:1833; py-spy there, SIGUSR1+faulthandler here: every
    worker registers a faulthandler dump on SIGUSR1 at startup)."""
    import glob as _glob
    import signal
    import time as _time

    # node-local (like `ray stack`): find worker processes via /proc —
    # the state API only lists actor processes, not idle task workers
    pids = []
    for p in _glob.glob("/proc/[0-9]*/cmdline"):
        try:
            with open(p, "rb") as fh:
                cmdline = fh.read().replace(b"\0", b" ")
        except OSError:
            continue
        # zygote-forked workers keep the fork-server's cmdline, so match
        # both spawn paths (a fork only rewrites argv if the child execs)
        if (b"ray_tpu._private.workers.default_worker" in cmdline
                or b"ray_tpu._private.workers.zygote" in cmdline):
            pids.append(int(p.split("/")[2]))
    if not pids:
        print("no live workers")
        return 0
    from ray_tpu._private.config import CONFIG

    log_dir = args.log_dir or os.path.join(CONFIG.log_dir, "workers")
    marks = {}
    for f in _glob.glob(os.path.join(log_dir, "worker-*.log")):
        marks[f] = os.path.getsize(f)
    signaled = []
    for pid in pids:
        try:
            os.kill(pid, signal.SIGUSR1)
            signaled.append(pid)
        except (ProcessLookupError, PermissionError):
            pass
    _time.sleep(0.5)  # give faulthandler time to write
    print(f"signaled {len(signaled)} workers: {signaled}")
    for f, start in sorted(marks.items()):
        try:
            size = os.path.getsize(f)
        except OSError:
            continue
        if size > start:
            with open(f, "rb") as fh:
                fh.seek(start)
                new = fh.read().decode(errors="replace")
            print(f"\n===== {os.path.basename(f)} =====\n{new}")
    return 0


def cmd_debug(args) -> int:
    """`ray-tpu debug` — attach to a waiting RemotePdb session (reference:
    ray debug — scripts.py:205 + util/rpdb.py); `ray-tpu debug postmortem`
    — merge per-process crash flight-recorder dumps (plus the live GCS
    event log when a cluster is reachable) into one causally ordered
    cluster timeline."""
    if getattr(args, "debug_cmd", None) == "postmortem":
        return _cmd_debug_postmortem(args)
    _connect(args)
    from ray_tpu.util import rpdb

    sessions = rpdb.list_sessions()
    if args.list:  # machine-readable, even (especially) when empty
        print(json.dumps(sessions, indent=2))
        return 0
    if not sessions:
        print("No active debug sessions (tasks call "
              "ray_tpu.util.rpdb.set_trace() to open one).")
        return 0
    choice = args.session
    if choice is None:
        for i, s in enumerate(sessions):
            print(f"[{i}] session {s['session_id']} "
                  f"pid={s['pid']} {s['host']}:{s['port']}")
        choice = 0 if len(sessions) == 1 else int(
            input("attach to which session? "))
    rpdb.connect(sessions[int(choice)])
    return 0


def _cmd_debug_postmortem(args) -> int:
    """Reconstruct a chaos/crash scenario offline: every process that died
    with its flight recorder armed left a flight-*.json in the session
    dir (chaos `kill` dumps explicitly before os._exit); survivors'
    events live in the GCS event manager. Merged and causally ordered,
    the result reads as one story: the injection, the FSM transitions it
    caused, and the recovery decision that followed."""
    from ray_tpu._private import event_log

    cluster_events = None
    gcs_addr = args.address or os.environ.get("RT_ADDRESS")
    if gcs_addr:
        from ray_tpu._private.rpc import EventLoopThread, RpcClient

        lt = EventLoopThread("postmortem-cli")
        try:
            cluster_events = RpcClient(gcs_addr, lt).call(
                "get_cluster_events", {"limit": 100_000}, timeout=10)
        except Exception as e:  # noqa: BLE001 — offline post-mortems are
            # the point: a dead cluster must not block the merge
            print(f"(GCS at {gcs_addr} unreachable: {e}; merging flight "
                  "dumps only)", file=sys.stderr)
        finally:
            lt.stop()
    flight = args.flight_dir or event_log.flight_dir()
    dumps = event_log.load_flight_dumps(flight)
    timeline = event_log.postmortem_timeline(
        flight, cluster_events, task_id=args.task_id,
        trace_id=getattr(args, "trace_id", None))
    if args.output:
        with open(args.output, "w") as f:
            json.dump(timeline, f, indent=2, default=str)
        print(f"wrote {len(timeline)} merged events to {args.output}")
        return 0
    print(f"# {len(dumps)} flight dump(s) in {flight}; "
          f"{len(cluster_events or [])} live GCS events; "
          f"{len(timeline)} merged")
    for d in dumps:
        print(f"#   pid={d.get('pid')} proc={d.get('proc')} "
              f"reason={d.get('reason')}")
    if not timeline:
        print("no events to merge (no dumps and no reachable GCS)")
        return 1
    print(event_log.format_events(timeline))
    return 0


def cmd_microbenchmark(args) -> int:
    from ray_tpu._private.ray_perf import main as perf_main

    perf_main(quick=args.quick)
    return 0


def cmd_lint(args) -> int:
    """Framework-invariant static analysis (`ray-tpu lint`): runs the
    tools/raylint checks (blocking-in-handler, lock-order,
    rpc-surface-drift, swallowed-recovery-error, spec-serialization-drift)
    over the tree. Fast and JAX-free — this is the tier-1-adjacent CI
    gate; the dynamic half is RAY_TPU_SANITIZE=1 (lock_sanitizer)."""
    try:
        from tools.raylint.__main__ import main as lint_main
    except ImportError:
        # installed-package invocation: tools/ lives next to ray_tpu/ in a
        # source checkout, not on sys.path — add the repo root
        import ray_tpu as _rt

        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(_rt.__file__)))
        if not os.path.isdir(os.path.join(repo_root, "tools", "raylint")):
            print("ray-tpu lint needs a source checkout (tools/raylint/ "
                  "not found)", file=sys.stderr)
            return 2
        sys.path.insert(0, repo_root)
        from tools.raylint.__main__ import main as lint_main
    argv = list(args.paths or [])
    if args.json:
        argv.append("--json")
    if args.select:
        argv += ["--select", args.select]
    if args.disable:
        argv += ["--disable", args.disable]
    if args.root:
        argv += ["--root", args.root]
    if args.list_checks:
        argv.append("--list-checks")
    return lint_main(argv)


def cmd_drain_node(args) -> int:
    """Gracefully drain a node (reference: `ray drain-node`,
    scripts.py:2268): the node stops taking leases, running work finishes
    (or is killed at the deadline), then the node unregisters."""
    from ray_tpu._private.rpc import EventLoopThread, RpcClient

    gcs_addr = args.address or os.environ.get("RT_ADDRESS")
    if not gcs_addr:
        print("--address (or RT_ADDRESS) is required", file=sys.stderr)
        return 1
    lt = EventLoopThread("drain-cli")
    try:
        gcs = RpcClient(gcs_addr, lt)
        nodes = gcs.call("get_all_node_info", {}, timeout=10)
        matches = [n for n in nodes
                   if n.alive and n.node_id.hex().startswith(args.node_id)]
        if not matches:
            print(f"no alive node with id prefix {args.node_id!r}",
                  file=sys.stderr)
            return 1
        if len(matches) > 1:
            print(f"ambiguous node id prefix {args.node_id!r} matches "
                  f"{len(matches)} nodes", file=sys.stderr)
            return 1
        node = matches[0]
        reply = gcs.call(
            "drain_node",
            {"node_id": node.node_id, "reason": args.reason,
             "deadline_s": args.deadline},
            timeout=15)
        if reply.get("status") not in ("ok", "already_draining"):
            print(f"drain failed: {reply}", file=sys.stderr)
            return 1
        print(f"node {node.node_id.hex()[:12]} draining "
              f"({reply.get('raylet', {}).get('active_leases', 0)} leases "
              "still running)")
        if args.wait:
            deadline = time.time() + args.deadline + 30
            while time.time() < deadline:
                alive = gcs.call(
                    "check_alive", {"node_ids": [node.node_id]}, timeout=10)
                if not alive.get(node.node_id, False):
                    print("node drained and unregistered")
                    return 0
                time.sleep(0.5)
            print("timed out waiting for the drain to finish",
                  file=sys.stderr)
            return 1
        return 0
    finally:
        lt.stop()


def cmd_preempt_node(args) -> int:
    """`ray-tpu preempt-node`: deliver a preemption ADVANCE NOTICE to a
    node (the announced-node-loss sibling of drain-node): scheduling
    excludes it immediately, training gangs checkpoint-and-drain, serve
    replicas deregister-then-drain, and the raylet kills stragglers only
    at the deadline. Models the cloud provider's preemptible-TPU notice
    for operators and drills alike."""
    from ray_tpu._private.rpc import EventLoopThread, RpcClient

    gcs_addr = args.address or os.environ.get("RT_ADDRESS")
    if not gcs_addr:
        print("--address (or RT_ADDRESS) is required", file=sys.stderr)
        return 1
    lt = EventLoopThread("preempt-cli")
    try:
        gcs = RpcClient(gcs_addr, lt)
        nodes = gcs.call("get_all_node_info", {}, timeout=10)
        matches = [n for n in nodes
                   if n.alive and n.node_id.hex().startswith(args.node_id)]
        if len(matches) != 1:
            print(f"node id prefix {args.node_id!r} matches "
                  f"{len(matches)} alive nodes", file=sys.stderr)
            return 1
        reply = gcs.call(
            "preempt_node",
            {"node_id": matches[0].node_id, "reason": args.reason,
             "deadline_s": args.deadline},
            timeout=15)
        if reply.get("status") not in ("ok", "already_draining"):
            print(f"preempt failed: {reply}", file=sys.stderr)
            return 1
        if reply.get("status") == "already_draining":
            # idempotent, like drain-node: the notice is already in
            # effect — a retried command must not read as a failure
            print(f"node {matches[0].node_id.hex()[:12]} is already "
                  "draining")
            return 0
        print(f"node {matches[0].node_id.hex()[:12]} notified: "
              f"{args.deadline:.0f}s to checkpoint-and-drain "
              f"({reply.get('raylet', {}).get('active_leases', 0)} leases, "
              f"{reply.get('raylet', {}).get('active_bundles', 0)} bundles "
              "on notice)")
        return 0
    finally:
        lt.stop()


def _parse_budget(raw: str) -> float:
    """'500ms' / '120s' / '2m' / '1h' / plain seconds."""
    text = raw.strip().lower()
    mult = 1.0
    if text.endswith("ms"):
        text, mult = text[:-2], 1e-3
    elif text.endswith("s"):
        text = text[:-1]
    elif text.endswith("m"):
        text, mult = text[:-1], 60.0
    elif text.endswith("h"):
        text, mult = text[:-1], 3600.0
    try:
        return float(text) * mult
    except ValueError:
        raise ValueError(
            f"bad duration {raw!r} (expected e.g. 500ms, 120s, 2m, 1h)"
        ) from None


def cmd_drill(args) -> int:
    """`ray-tpu drill` — scheduled chaos drills with SLO verdicts:
    `run` executes one seeded scenario against a live self-contained
    cluster + workload and writes a JSON report whose MTTR/availability
    derive from the cluster event log; `report` pretty-prints a report
    artifact or recomputes one offline from saved events; `list` shows
    scenarios and their thresholds. --gate exits 1 on a failed verdict
    (the CI wiring: tools/ci.sh)."""
    from ray_tpu import drills

    if args.drill_cmd == "list":
        thresholds = drills.load_thresholds(args.thresholds)
        out = {name: thresholds.get(name, {})
               for name in sorted(drills.SCENARIO_CLASSES)}
        print(json.dumps(out, indent=2))
        return 0

    if args.drill_cmd == "report":
        if args.from_events:
            try:
                report = drills.report_from_events(
                    args.from_events, scenario=args.scenario,
                    seed=args.seed, thresholds_path=args.thresholds)
            except ValueError as e:
                print(f"drill report: {e}", file=sys.stderr)
                return 1
        elif args.report:
            with open(args.report) as f:
                report = json.load(f)
        else:
            print("drill report needs --report FILE or --from-events FILE",
                  file=sys.stderr)
            return 1
        if args.json:
            print(drills.slo.dumps_report(report))
        else:
            _print_drill_report(report)
        return 0 if (not args.gate or report["verdict"]["passed"]) else 1

    # run
    scenario = args.scenario or "replica_kill"
    seed = 0 if args.seed is None else args.seed
    report_path = args.report or os.path.join(
        ".", f"drill_{scenario}_seed{seed}.json")
    try:
        budget_s = _parse_budget(args.budget)
    except ValueError as e:
        print(f"drill run: {e}", file=sys.stderr)
        return 2
    config = drills.DrillConfig(
        scenario=scenario, seed=seed,
        budget_s=budget_s,
        rate_hz=args.rate, report_path=report_path,
        thresholds_path=args.thresholds)
    report = drills.run_drill(config)
    if args.json:
        print(drills.slo.dumps_report(report))
    else:
        _print_drill_report(report)
        print(f"report: {report_path} "
              f"(events: {report_path}.events.json)")
    return 0 if (not args.gate or report["verdict"]["passed"]) else 1


def _print_drill_report(report: dict) -> None:
    v = report["verdict"]
    s = report["slo"]
    print(f"drill {report['scenario']} (seed={report['seed']}): "
          f"{'PASS' if v['passed'] else 'FAIL'}")
    print(f"  fingerprint : {report['fingerprint']}")
    print(f"  MTTR        : max={s['mttr_max_s']}s mean={s['mttr_mean_s']}s "
          f"({len(s['timeline'])} injection(s))")
    print(f"  availability: {s['availability']} "
          f"over {s['windows']} window(s) {s['requests']}")
    print(f"  lost        : {s['lost_accepted']} accepted request(s)")
    if s.get("preempt_notices") or s.get("checkpoint_drains"):
        print(f"  preemption  : {s['preempt_notices']} notice(s), "
              f"{s['checkpoint_drains']} gang drain(s)")
    ctl = s.get("controller")
    if ctl:
        print(f"  controller  : incarnation {ctl.get('incarnation')} "
              f"adopted={ctl.get('adopted_replicas')} "
              f"restarted={ctl.get('restarted_replicas')} "
              f"fresh_replicas={ctl.get('fresh_replicas_started')}")
    for row in s["timeline"]:
        print(f"    inject {row['detail']} -> "
              f"{row['recovery_type'] or 'NO RECOVERY'} "
              f"mttr={row['mttr_s']}s")
    wl = report.get("workload") or {}
    if wl.get("kind") == "training":
        print(f"  training    : steps={wl.get('steps_reported')} "
              f"resume_points={wl.get('resume_points')} "
              f"loss_continuous={wl.get('loss_continuous')}")
    for f in v["failures"]:
        print(f"  FAIL: {f}")


def cmd_healthcheck(args) -> int:
    """Liveness probe (reference: `ray health-check`, scripts.py:2365):
    exit 0 iff the GCS answers a ping — usable as a container/systemd
    health check without starting a driver."""
    from ray_tpu._private.rpc import EventLoopThread, RpcClient

    gcs_addr = args.address or os.environ.get("RT_ADDRESS")
    if not gcs_addr:
        print("--address (or RT_ADDRESS) is required", file=sys.stderr)
        return 1
    lt = EventLoopThread("healthcheck-cli")
    try:
        reply = RpcClient(gcs_addr, lt).call(
            "gcs_ping", {}, timeout=args.timeout)
        ok = reply.get("status") == "ok"
        print("ok" if ok else f"unhealthy: {reply}")
        return 0 if ok else 1
    except Exception as e:  # noqa: BLE001 — any failure means unhealthy
        print(f"unhealthy: {e}", file=sys.stderr)
        return 1
    finally:
        lt.stop()


def _fmt_alert_value(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.3g}"
    return str(v)


def cmd_health(args) -> int:
    """`ray-tpu health`: live SLO scorecard + demand signals from the GCS
    health plane (metrics store, burn-rate engine, demand bus)."""
    from ray_tpu._private.rpc import EventLoopThread, RpcClient

    gcs_addr = args.address or os.environ.get("RT_ADDRESS")
    if not gcs_addr:
        print("--address (or RT_ADDRESS) is required", file=sys.stderr)
        return 1
    lt = EventLoopThread("health-cli")
    try:
        reply = RpcClient(gcs_addr, lt).call("get_health", {}, timeout=10)
    except Exception as e:  # noqa: BLE001 — unreachable GCS is the answer
        print(f"health query failed: {e}", file=sys.stderr)
        return 1
    finally:
        lt.stop()
    if args.json:
        print(json.dumps(reply, indent=2, default=str))
        firing = [r for r in reply.get("scorecard", []) if r.get("firing")]
        return 1 if firing else 0
    return render_health(reply)


def render_health(reply: dict) -> int:
    scorecard = reply.get("scorecard", [])
    firing = [r for r in scorecard if r.get("firing")]
    print(f"cluster health @ {time.strftime('%H:%M:%S', time.localtime(reply.get('time', time.time())))}"
          f" — {len(firing)} alert(s) firing, {len(scorecard)} rules")
    print("  SLO scorecard:")
    for row in scorecard:
        state = "FIRING" if row.get("firing") else "ok"
        line = (f"    [{state:>6}] {row['rule']:<28} {row['severity']:<7}"
                f" value={_fmt_alert_value(row.get('value'))}"
                f" threshold={_fmt_alert_value(row.get('threshold'))}")
        print(line)
        if row.get("firing") and row.get("description"):
            print(f"             {row['description']}")
    demand = reply.get("demand") or {}
    serve = demand.get("serve") or {}
    rl = demand.get("rl") or {}
    pending = demand.get("pending") or {}
    print("  demand signals:")
    print(f"    serve : queue={_fmt_alert_value(serve.get('queue_depth'))}"
          f" req/s={_fmt_alert_value(serve.get('request_rate'))}"
          f" ok/s={_fmt_alert_value(serve.get('ok_rate'))}"
          f" shed/s={_fmt_alert_value(serve.get('shed_rate'))}"
          f" ttft_p99={_fmt_alert_value(serve.get('ttft_p99_s'))}s")
    print(f"    rl    : shed/s={_fmt_alert_value(rl.get('sample_shed_rate'))}"
          f" stale/s={_fmt_alert_value(rl.get('stale_drop_rate'))}")
    print(f"    sched : pending_pg_bundles="
          f"{_fmt_alert_value(pending.get('pg_bundles'))}"
          f" task_demands={_fmt_alert_value(pending.get('task_demands'))}"
          f" nodes_alive={_fmt_alert_value(demand.get('nodes_alive'))}")
    for res, pool in sorted((demand.get("pools") or {}).items()):
        print(f"    pool  : {res:<8} util="
              f"{_fmt_alert_value(pool.get('utilization'))}"
              f" ({_fmt_alert_value(pool.get('available'))}"
              f"/{_fmt_alert_value(pool.get('total'))} free)")
    store = reply.get("store") or {}
    print(f"  store : {store.get('series', 0)} series, "
          f"{store.get('points_ingested', 0)} points ingested, "
          f"{store.get('series_dropped', 0)} series dropped, "
          f"{len(reply.get('push_sources') or [])} push sources")
    return 1 if firing else 0


def cmd_alerts(args) -> int:
    """`ray-tpu alerts [--history]`: active SLO alerts (and recent
    fire/resolve transitions) from the GCS SLO engine."""
    from ray_tpu._private.rpc import EventLoopThread, RpcClient

    gcs_addr = args.address or os.environ.get("RT_ADDRESS")
    if not gcs_addr:
        print("--address (or RT_ADDRESS) is required", file=sys.stderr)
        return 1
    lt = EventLoopThread("alerts-cli")
    try:
        reply = RpcClient(gcs_addr, lt).call("get_alerts", {}, timeout=10)
    except Exception as e:  # noqa: BLE001
        print(f"alert query failed: {e}", file=sys.stderr)
        return 1
    finally:
        lt.stop()
    if args.json:
        print(json.dumps(reply, indent=2, default=str))
        return 1 if reply.get("active") else 0
    return render_alerts(reply, history=args.history)


def render_alerts(reply: dict, history: bool = False) -> int:
    active = reply.get("active") or []
    if not active:
        print("no alerts firing")
    for a in active:
        fired = time.strftime("%H:%M:%S", time.localtime(a.get("fired_at", 0)))
        print(f"  FIRING {a['rule']:<28} {a.get('severity', '?'):<7} "
              f"since {fired} value={_fmt_alert_value(a.get('value'))}")
    if history:
        rows = reply.get("history") or []
        print(f"  history ({len(rows)} transitions, newest last):")
        for h in rows:
            t = time.strftime("%H:%M:%S", time.localtime(h.get("time", 0)))
            extra = (f"after {_fmt_alert_value(h.get('duration_s'))}s"
                     if h.get("type") == "alert.resolved"
                     else f"value={_fmt_alert_value(h.get('value'))}")
            print(f"    {t} {h.get('type', '?'):<15} "
                  f"{h.get('rule', '?'):<28} {extra}")
    return 1 if active else 0


# --------------------------------------------------------------------- main


def main(argv=None) -> int:
    p = argparse.ArgumentParser("ray-tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    sp = sub.add_parser("start", help="start a head or worker node process")
    sp.add_argument("--head", action="store_true")
    sp.add_argument("--address", help="GCS address to join as a worker")
    sp.add_argument("--port", type=int, default=0)
    sp.add_argument("--num-cpus", type=float, default=None)
    sp.add_argument("--resources", help="JSON resource dict")
    sp.add_argument("--dashboard-port", type=int, default=8265,
                    help="-1 disables the dashboard; 0 picks a free port")
    sp.add_argument("--block", action="store_true", default=True)
    sp.add_argument("--no-block", dest="block", action="store_false")
    sp.set_defaults(fn=cmd_start)

    sp = sub.add_parser("stop", help="stop locally-started node processes")
    sp.set_defaults(fn=cmd_stop)

    sp = sub.add_parser("up", help="launch a cluster from a YAML config")
    sp.add_argument("config", help="cluster YAML path")
    sp.add_argument("--no-restart", action="store_true",
                    help="re-sync/setup without restarting running nodes")
    sp.add_argument("--min-workers", type=int, default=None)
    sp.set_defaults(fn=cmd_up)

    sp = sub.add_parser("down", help="tear down a launched cluster")
    sp.add_argument("config", help="cluster YAML path")
    sp.add_argument("--workers-only", action="store_true")
    sp.set_defaults(fn=cmd_down)

    sp = sub.add_parser("exec", help="run a command on the head node")
    sp.add_argument("config", help="cluster YAML path")
    sp.add_argument("command", help="shell command to run")
    sp.set_defaults(fn=cmd_exec)

    sp = sub.add_parser("attach", help="interactive shell on the head node")
    sp.add_argument("config", help="cluster YAML path")
    sp.set_defaults(fn=cmd_attach)

    sp = sub.add_parser("rsync-up", help="copy local files to the head")
    sp.add_argument("config"); sp.add_argument("source")
    sp.add_argument("target")
    sp.set_defaults(fn=cmd_rsync_up)

    sp = sub.add_parser("rsync-down", help="copy files from the head")
    sp.add_argument("config"); sp.add_argument("source")
    sp.add_argument("target")
    sp.set_defaults(fn=cmd_rsync_down)

    sp = sub.add_parser("get-head-ip", help="print the head node IP")
    sp.add_argument("config")
    sp.set_defaults(fn=cmd_get_head_ip)

    sp = sub.add_parser("status", help="cluster nodes + resources")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_status)

    sp = sub.add_parser("submit", help="submit a job (entrypoint command)")
    sp.add_argument("--address")
    sp.add_argument("--runtime-env", help="JSON runtime env")
    sp.add_argument("--no-wait", action="store_true")
    sp.add_argument("entrypoint", nargs=argparse.REMAINDER)
    sp.set_defaults(fn=cmd_submit)

    sp = sub.add_parser("job", help="job operations")
    sp.add_argument("--address")
    sp.add_argument("job_cmd", choices=["list", "status", "logs", "stop"])
    sp.add_argument("id", nargs="?")
    sp.set_defaults(fn=cmd_job)

    sp = sub.add_parser("list", help="list cluster state")
    sp.add_argument("kind", choices=["nodes", "actors", "tasks", "jobs",
                                     "placement-groups", "objects",
                                     "workers"])
    sp.add_argument("--address")
    sp.add_argument("--limit", type=int, default=100)
    sp.set_defaults(fn=cmd_list)

    sp = sub.add_parser(
        "memory", help="cluster-wide object/KV memory report + leak sweep")
    sp.add_argument("--address")
    sp.add_argument("--group-by", choices=["owner", "node"],
                    help="aggregate the reference table per owner or node")
    sp.add_argument("--top", type=int, default=20,
                    help="show only the top N rows by size (0 = all)")
    sp.add_argument("--stats-only", action="store_true",
                    help="occupancy counters only, skip per-ref tables")
    sp.add_argument("--leaks", action="store_true",
                    help="run the leak sweep (exit 1 if suspects found)")
    sp.add_argument("--max-age", type=float, default=3600.0,
                    help="pin/borrow age (s) before it becomes a suspect")
    sp.add_argument("--min-orphan-age", type=float, default=30.0,
                    help="grace (s) before an unreferenced entry is an "
                         "orphan suspect")
    sp.add_argument("--timeout", type=float, default=30.0,
                    help="per-node fan-out timeout (s)")
    sp.add_argument("--local", action="store_true",
                    help="driver-local snapshot only (no cluster fan-out)")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_memory)

    sp = sub.add_parser("timeline", help="dump chrome trace of task events")
    sp.add_argument("--address")
    sp.add_argument("-o", "--output")
    sp.add_argument("--limit", type=int, default=100_000,
                    help="max raw task events to fetch (default 100000)")
    sp.add_argument("--task-id", help="only this task's spans")
    sp.set_defaults(fn=cmd_timeline)

    sp = sub.add_parser(
        "latency", help="per-stage latency breakdown of recent tasks")
    sp.add_argument("--address")
    sp.add_argument("-n", type=int, default=20,
                    help="show the last N finished tasks")
    sp.set_defaults(fn=cmd_latency)

    sp = sub.add_parser(
        "events", help="cluster-wide structured lifecycle event log")
    sp.add_argument("--address")
    sp.add_argument("--type", help='event-type glob (e.g. "actor.*", '
                                   '"chaos.inject", "task.retry")')
    sp.add_argument("--task-id", help="only events referencing this task")
    sp.add_argument("--actor-id", help="only events referencing this actor")
    sp.add_argument("--node-id", help="only events referencing this node")
    sp.add_argument("--limit", type=int, default=1000)
    sp.add_argument("--causal", action="store_true",
                    help="with --task-id: the task's full causal timeline "
                         "(state transitions + retries + decisions merged)")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_events)

    sp = sub.add_parser(
        "trace", help="cross-process span tree of one traced request")
    sp.add_argument("trace_id", nargs="?",
                    help="trace id (a response's X-Trace-Id header, an "
                         "event's trace= field, or `ray-tpu trace --list`)")
    sp.add_argument("--address")
    sp.add_argument("--list", action="store_true",
                    help="list recent sampled/force-kept traces")
    sp.add_argument("--limit", type=int, default=50,
                    help="traces to list (with --list)")
    sp.add_argument("--chrome", metavar="FILE",
                    help="export the trace as a chrome://tracing file "
                         "with cross-process flow arrows")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_trace)

    sp = sub.add_parser("serve", help="serve deploy/status/shutdown")
    sp.add_argument("serve_cmd", choices=["deploy", "status", "shutdown"])
    sp.add_argument("config", nargs="?", help="JSON config (deploy)")
    sp.add_argument("--address")
    sp.set_defaults(fn=cmd_serve)

    sp = sub.add_parser("llm", help="LLM serving status (serve.llm apps)")
    sp.add_argument("llm_cmd", choices=["status"])
    sp.add_argument("--address")
    sp.add_argument("--json", action="store_true",
                    help="machine-readable output")
    sp.set_defaults(fn=cmd_llm)

    sp = sub.add_parser("logs", help="tail worker logs across the cluster")
    sp.add_argument("--address")
    sp.add_argument("--pid", type=int, help="only this worker pid")
    sp.add_argument("--node-id", help="node id (prefix) filter")
    sp.add_argument("--lines", type=int, default=50)
    sp.add_argument("--all", action="store_true",
                    help="include workers with empty logs")
    sp.set_defaults(fn=cmd_logs)

    sp = sub.add_parser("metrics", help="metrics tooling")
    sp.add_argument("metrics_cmd",
                    choices=["grafana-dashboard", "launch-prometheus"])
    sp.add_argument("-o", "--output")
    sp.add_argument("--scrape-target",
                    help="host:port of the dashboard /metrics endpoint")
    sp.set_defaults(fn=cmd_metrics)

    sp = sub.add_parser("drain-node", help="gracefully drain a node")
    sp.add_argument("--address")
    sp.add_argument("--node-id", required=True,
                    help="node id (hex, prefix ok)")
    sp.add_argument("--reason", default="")
    sp.add_argument("--deadline", type=float, default=300.0,
                    help="seconds before running work is killed")
    sp.add_argument("--wait", action="store_true",
                    help="block until the node unregisters")
    sp.set_defaults(fn=cmd_drain_node)

    sp = sub.add_parser("preempt-node",
                        help="deliver a preemption advance notice "
                             "(checkpoint-and-drain window) to a node")
    sp.add_argument("--address")
    sp.add_argument("--node-id", required=True,
                    help="node id (hex, prefix ok)")
    sp.add_argument("--reason", default="operator preemption")
    sp.add_argument("--deadline", type=float, default=30.0,
                    help="notice window before stragglers are killed")
    sp.set_defaults(fn=cmd_preempt_node)

    sp = sub.add_parser("drill",
                        help="chaos drills with event-log-derived SLO "
                             "verdicts (MTTR, availability, request loss)")
    sp.add_argument("drill_cmd", choices=["run", "report", "list"])
    sp.add_argument("--scenario", default=None,
                    help="see `ray-tpu drill list` (run default: "
                         "replica_kill; report: taken from the artifact)")
    sp.add_argument("--seed", type=int, default=None,
                    help="same seed => same victims + fingerprint "
                         "(run default: 0; report: from the artifact)")
    sp.add_argument("--budget", default="120s",
                    help="drill budget, e.g. 120s or 2m")
    sp.add_argument("--rate", type=float, default=30.0,
                    help="serving workload offered load (rps)")
    sp.add_argument("--report", help="report artifact path "
                                     "(run: write; report: read)")
    sp.add_argument("--from-events",
                    help="report: recompute SLOs from a saved "
                         "*.events.json artifact (deterministic)")
    sp.add_argument("--thresholds",
                    help="thresholds JSON (default: drills/thresholds.json)")
    sp.add_argument("--gate", action="store_true",
                    help="exit 1 when the verdict fails (CI)")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_drill)

    sp = sub.add_parser("healthcheck", help="exit 0 iff the GCS is healthy")
    sp.add_argument("--address")
    sp.add_argument("--timeout", type=float, default=5.0)
    sp.set_defaults(fn=cmd_healthcheck)

    sp = sub.add_parser(
        "health", help="SLO scorecard + demand signals (exit 1 if firing)")
    sp.add_argument("--address")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_health)

    sp = sub.add_parser(
        "alerts", help="active SLO alerts (exit 1 if any firing)")
    sp.add_argument("--address")
    sp.add_argument("--history", action="store_true",
                    help="also print recent fire/resolve transitions")
    sp.add_argument("--json", action="store_true")
    sp.set_defaults(fn=cmd_alerts)

    sp = sub.add_parser("kill-random-node",
                        help="chaos: ungracefully kill a random worker node")
    sp.add_argument("--address")
    sp.add_argument("--yes", action="store_true")
    sp.set_defaults(fn=cmd_kill_random_node)

    sp = sub.add_parser(
        "chaos", help="message-level fault injection (seeded, deterministic)")
    sp.add_argument("chaos_cmd", choices=["start", "stop", "status"])
    sp.add_argument("--address")
    sp.add_argument("--plan", help="JSON chaos plan file (see README)")
    sp.add_argument("--seed", type=int, help="override the plan's seed")
    sp.add_argument("--kill-point",
                    choices=["before_execute", "after_reply", "mid_stream"],
                    help="one-rule plan: kill a process at this point")
    sp.add_argument("--method", default="*",
                    help="RPC method glob for --kill-point")
    sp.add_argument("--label", default="*",
                    help="endpoint label glob (gcs|raylet|driver|worker)")
    sp.add_argument("--p", type=float, default=1.0)
    sp.add_argument("--after", type=int, default=0)
    sp.add_argument("--times", type=int)
    sp.add_argument("--yes", action="store_true")
    sp.set_defaults(fn=cmd_chaos)

    sp = sub.add_parser("client-server",
                        help="run the client proxy for remote drivers")
    sp.add_argument("--address", help="GCS address of the cluster")
    sp.add_argument("--host", default="127.0.0.1")
    sp.add_argument("--port", type=int, default=10001)
    sp.add_argument("--token", help="shared auth token (RT_CLIENT_TOKEN)")
    sp.set_defaults(fn=cmd_client_server)

    sp = sub.add_parser("profile",
                        help="CPU flamegraph / heap snapshot of a worker, "
                             "or --device for the cluster device-plane "
                             "phase report")
    sp.add_argument("--address")
    sp.add_argument("--pid", type=int,
                    help="target worker pid (required for --cpu/--memory; "
                         "--device fans out to every worker)")
    sp.add_argument("--duration", type=float, default=5.0)
    sp.add_argument("--memory", action="store_true",
                    help="heap snapshot (tracemalloc) instead of CPU; a "
                         "cold worker samples for --duration in one call")
    sp.add_argument("--memory-stop", action="store_true",
                    help="take a final heap snapshot and STOP tracemalloc "
                         "in the worker (disarms the per-allocation "
                         "overhead a prior --memory run left behind)")
    sp.add_argument("--folded", action="store_true",
                    help="with --memory: flamegraph-compatible folded "
                         "heap stacks instead of JSON")
    sp.add_argument("--device", action="store_true",
                    help="device-plane phase report (ISSUE 15): fan "
                         "per-worker step/decode phase attributions "
                         "(input_wait/h2d/compile/device_execute/reply), "
                         "MFU and HBM occupancy out of every raylet")
    sp.add_argument("--chrome",
                    help="with --device: write ONE chrome trace merging "
                         "device phase lanes with PR 1 task-stage spans")
    sp.add_argument("--recent", type=int, default=64,
                    help="device steps per profiler in the chrome export")
    sp.add_argument("--json", action="store_true",
                    help="with --device: raw JSON reports")
    sp.add_argument("--top", type=int, default=40)
    sp.set_defaults(fn=cmd_profile)

    sp = sub.add_parser("stack", help="dump python stacks of node workers")
    sp.add_argument("--address")
    sp.add_argument("--log-dir")
    sp.set_defaults(fn=cmd_stack)

    sp = sub.add_parser("debug", help="attach to a remote pdb session, or "
                                      "`debug postmortem` to merge crash "
                                      "flight-recorder dumps")
    sp.add_argument("debug_cmd", nargs="?", choices=["postmortem"],
                    help="postmortem: merge per-process flight dumps + the "
                         "GCS event log into one causal cluster timeline")
    sp.add_argument("--address")
    sp.add_argument("--list", action="store_true",
                    help="list sessions as JSON and exit")
    sp.add_argument("--session", help="session index to attach")
    sp.add_argument("--flight-dir",
                    help="flight-dump dir (default: <session>/flight)")
    sp.add_argument("--task-id", help="postmortem: only this task's events")
    sp.add_argument("--trace-id",
                    help="postmortem: only events stamped with this "
                         "distributed trace id (`ray-tpu trace` links "
                         "back the other way)")
    sp.add_argument("-o", "--output",
                    help="postmortem: write merged JSON here")
    sp.set_defaults(fn=cmd_debug)

    sp = sub.add_parser("microbenchmark", help="run the core benchmark suite")
    sp.add_argument("--quick", action="store_true")
    sp.set_defaults(fn=cmd_microbenchmark)

    sp = sub.add_parser("lint", help="framework-invariant static analysis "
                                     "(tools/raylint)")
    sp.add_argument("paths", nargs="*", help="files/dirs (default: ray_tpu)")
    sp.add_argument("--json", action="store_true")
    sp.add_argument("--select", help="comma-separated check names")
    sp.add_argument("--disable", help="comma-separated check names to skip")
    sp.add_argument("--root", help="project root (default: auto-detect)")
    sp.add_argument("--list-checks", action="store_true")
    sp.set_defaults(fn=cmd_lint)

    args = p.parse_args(argv)
    return args.fn(args)
