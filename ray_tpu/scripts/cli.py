"""CLI: ray-tpu start/stop/status/submit/memory/metrics/timeline/summary.

Analog of the reference's scripts (reference: python/ray/scripts/
scripts.py — start:532, stop:980, status, memory, timeline, submit:1466;
`ray summary tasks` from state/state_cli.py).  Invoke as
``python -m ray_tpu.scripts.cli <cmd>`` (or the ray-tpu entrypoint when
installed).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time


def cmd_start(args):
    if not args.head:
        print("only --head start is supported in this round; workers join via raylet", file=sys.stderr)
        return 1
    res = {}
    if args.num_cpus is not None:
        res["CPU"] = args.num_cpus
    if args.num_tpus is not None:
        res["TPU"] = args.num_tpus
    session_dir = f"/tmp/ray_tpu/cli_{int(time.time())}"
    os.makedirs(session_dir, exist_ok=True)
    cmd = [
        sys.executable,
        "-m",
        "ray_tpu.gcs.head_main",
        "--host",
        args.host,
        "--port",
        str(args.port),
        "--session-dir",
        session_dir,
        "--resources",
        json.dumps(res),
    ]
    logf = open(os.path.join(session_dir, "head.log"), "ab")
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=logf, start_new_session=True)
    deadline = time.time() + 30
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line.startswith(b"PORT "):
            port = int(line.split()[1])
            with open("/tmp/ray_tpu/head_address", "w") as f:
                f.write(f"{args.host}:{port}\n{proc.pid}\n")
            print(f"head started at {args.host}:{port} (pid {proc.pid})")
            print(f"connect with: ray_tpu.init(address='{args.host}:{port}')")
            return 0
        if proc.poll() is not None:
            break
    print("head failed to start", file=sys.stderr)
    return 1


def _read_address(args):
    addr = getattr(args, "address", None)
    if addr:
        return addr
    try:
        with open("/tmp/ray_tpu/head_address") as f:
            return f.read().splitlines()[0]
    except OSError:
        print("no running head found (missing /tmp/ray_tpu/head_address)", file=sys.stderr)
        sys.exit(1)


def cmd_stop(args):
    try:
        with open("/tmp/ray_tpu/head_address") as f:
            lines = f.read().splitlines()
        pid = int(lines[1])
        os.kill(pid, 15)
        os.remove("/tmp/ray_tpu/head_address")
        print(f"stopped head (pid {pid})")
        return 0
    except (OSError, IndexError, ValueError) as e:
        print(f"stop failed: {e}", file=sys.stderr)
        return 1


def cmd_status(args):
    import ray_tpu

    ray_tpu.init(address=_read_address(args))
    total = ray_tpu.cluster_resources()
    avail = ray_tpu.available_resources()
    print("== cluster resources ==")
    for k in sorted(total):
        print(f"  {k}: {avail.get(k, 0):.1f}/{total[k]:.1f} available")
    print("== nodes ==")
    for n in ray_tpu.nodes():
        print(f"  {n['NodeID'][:12]} alive={n['Alive']} {n['Resources']}")
    from ray_tpu.experimental.state import list_actors

    actors = list_actors()
    alive = sum(1 for a in actors if a["state"] == "ALIVE")
    print(f"== actors == {alive} alive / {len(actors)} total")
    return 0


def cmd_memory(args):
    import ray_tpu
    from ray_tpu._private import worker as worker_mod

    ray_tpu.init(address=_read_address(args))
    cw = worker_mod._require_connected()
    store = cw.store
    print(
        f"object store: {store.used()}/{store.capacity()} bytes, "
        f"{store.num_objects()} objects, {store.evictions()} evictions"
    )
    return 0


def cmd_submit(args):
    from ray_tpu.job_submission import JobSubmissionClient

    client = JobSubmissionClient(address=_read_address(args))
    job_id = client.submit_job(entrypoint=" ".join(args.entrypoint))
    print(f"submitted {job_id}")
    if args.wait:
        status = client.wait_until_finish(job_id, timeout=args.timeout)
        print(f"{job_id}: {status}")
        print(client.get_job_logs(job_id))
        return 0 if status == "SUCCEEDED" else 1
    return 0


def cmd_metrics(args):
    import ray_tpu
    from ray_tpu.util import metrics as m

    ray_tpu.init(address=_read_address(args))
    sys.stdout.write(m.prometheus_text())
    return 0


def cmd_timeline(args):
    """Export the cluster timeline — task exec windows, flight-recorder
    per-phase sub-spans, cluster-event markers — as a chrome://tracing
    JSON file (reference: `ray timeline`, scripts.py:timeline)."""
    import ray_tpu

    ray_tpu.init(address=_read_address(args))
    out = args.output or f"/tmp/ray-tpu-timeline-{int(time.time())}.json"
    events = ray_tpu.timeline(filename=out)
    print(f"wrote {len(events)} events to {out}")
    print("open chrome://tracing and load the file to view")
    return 0


def _latency_table(rows, key_a, key_b, label_a, label_b):
    hdr = (
        f"{label_a:28s} {label_b:20s} {'count':>7s} {'p50':>10s} "
        f"{'p95':>10s} {'p99':>10s} {'max':>10s}"
    )
    print(hdr)
    print("-" * len(hdr))
    for r in rows:
        print(
            f"{str(r[key_a])[:28]:28s} {str(r[key_b])[:20]:20s} {r['count']:7d} "
            f"{r['p50'] * 1e3:9.2f}ms {r['p95'] * 1e3:9.2f}ms "
            f"{r.get('p99', r['p95']) * 1e3:9.2f}ms {r['max'] * 1e3:9.2f}ms"
        )


def _print_fleet_gauges(fleet: dict) -> None:
    """Serve fleet-survival block for `summary serve`: replica count,
    scale events, mid-stream failovers, drain outcomes per deployment."""
    if not fleet:
        return
    print("== serve fleet ==")
    for dep, g in sorted(fleet.items()):
        print(
            f"  {dep}: replicas={g.get('replicas', 0):.0f} "
            f"scale_out={g.get('scale_events_total:out', 0):.0f} "
            f"scale_in={g.get('scale_events_total:in', 0):.0f} "
            f"failovers={g.get('failovers_total', 0):.0f} "
            f"drained(clean={g.get('drained_total:clean', 0):.0f} "
            f"deadline={g.get('drained_total:deadline', 0):.0f})"
        )


def _print_engine_gauges(engine: dict) -> None:
    """Continuous-batching engine occupancy block shared by
    `summary serve` and `summary memory`."""
    if not engine:
        return
    print("== serve engine (continuous batching) ==")
    for dep, gauges in sorted(engine.items()):
        slots = gauges.get("slots:active", 0)
        total = gauges.get("slots:total", 0)
        pages = gauges.get("kv_pages:used", 0)
        ptotal = gauges.get("kv_pages:total", 0)
        print(
            f"  {dep}: slots={slots:.0f}/{total:.0f} "
            f"(prefill={gauges.get('slots:prefill', 0):.0f} "
            f"decode={gauges.get('slots:decode', 0):.0f}) "
            f"kv_pages={pages:.0f}/{ptotal:.0f} "
            f"queue={gauges.get('queue_depth', 0):.0f} "
            f"frag={gauges.get('page_fragmentation', 0):.2f} "
            f"host={gauges.get('host_share', 0):.2f} "
            f"cache={gauges.get('cache_bytes_per_position', 0):.0f}B/pos "
            + (f"state={gauges['state_bytes_per_slot']:.0f}B/slot " if "state_bytes_per_slot" in gauges else "")
            + f"tokens={gauges.get('tokens_total', 0):.0f}"
        )


def cmd_summary(args):
    """`ray-tpu summary tasks|serve|train|memory`: workload-plane latency
    and occupancy tables from the head's flight recorder."""
    import ray_tpu  # noqa: F401  (init side effect)
    from ray_tpu.experimental.state import summarize_workloads

    ray_tpu.init(address=_read_address(args))
    reply = summarize_workloads(args.what)
    if args.what == "memory":
        print("== shm stores (per node) ==")
        for nid, st in reply.get("nodes", {}).items():
            used = st.get("used", 0)
            cap = st.get("capacity", 0)
            print(
                f"  {nid[:12]} alive={st.get('alive')} "
                f"used={used:.0f}/{cap:.0f} bytes "
                f"objects={st.get('objects', 0):.0f} "
                f"evictions={st.get('evictions', 0):.0f}"
            )
        obj = reply.get("objects", {})
        print(
            f"== objects == total={obj.get('total', 0)} "
            f"pinned={obj.get('pinned', 0)} spilled={obj.get('spilled', 0)} "
            f"lineage={obj.get('lineage', 0)} by_state={obj.get('by_state')}"
        )
        for owner, st in sorted(obj.get("by_owner", {}).items()):
            print(f"  owner {owner}: {st['count']} objects, {st['bytes']} bytes")
        chans = reply.get("dag_channels", {})
        if chans:
            print("== dag channels ==")
            for key, st in sorted(chans.items()):
                print(
                    f"  {key[:40]:40s} occupancy={st.get('occupancy')}/"
                    f"{st.get('slots')} slots"
                )
        _print_engine_gauges(reply.get("serve_engine", {}))
        return 0
    if args.what == "head":
        print(
            f"== head == incarnation={reply.get('incarnation')} "
            f"restarts={reply.get('restarts_total')} "
            f"node={str(reply.get('head_node_id', ''))[:12]} "
            f"recovering={reply.get('recovering')}"
        )
        lr = reply.get("last_recovery")
        if lr:
            att = lr.get("reattached", {})
            reaped = lr.get("reaped", {})
            resub = lr.get("resubmits", {})
            print(
                f"  last recovery: {lr.get('duration_s', 0):.2f}s at "
                f"{time.strftime('%H:%M:%S', time.localtime(lr.get('at', 0)))} "
                f"(incarnation {lr.get('incarnation')})"
            )
            print(
                f"  reattached: {att.get('nodes', 0)} nodes, "
                f"{att.get('workers', 0)} workers, {att.get('drivers', 0)} "
                f"drivers, {att.get('actors', 0)} actors, "
                f"{att.get('tasks', 0)} running tasks, "
                f"{att.get('leases', 0)} leases"
            )
            print(
                f"  reaped: {reaped.get('actors', 0)} actors, "
                f"{reaped.get('owners', 0)} orphaned owners, "
                f"{reaped.get('locations', 0)} stale locations, "
                f"{reaped.get('spills', 0)} stale spills; resubmits "
                f"{resub.get('deduped', 0)}/{resub.get('received', 0)} deduped"
            )
        else:
            print("  no recovery this incarnation")
        return 0
    if args.what == "preemptions":
        counts = reply.get("counts", {})
        print(
            f"== preemptions == total={reply.get('total', 0)} "
            f"parked_actors={len(reply.get('parked', []))} "
            f"slo_hold={reply.get('slo_hold')}"
        )
        for key, n in sorted(counts.items()):
            print(f"  {key}: {n:.0f}")
        for rec in reply.get("preemptions", [])[-50:]:
            print(
                f"  {time.strftime('%H:%M:%S', time.localtime(rec['ts']))} "
                f"{rec['kind']:12s} band={rec['band']} -> "
                f"req_band={rec['requester_band']} "
                f"{rec.get('name') or rec.get('victim', '')} "
                f"{rec.get('reason', '')}"
            )
        return 0
    if args.what == "errors":
        counts = reply.get("counts", {})
        print(
            f"== errors == {reply.get('distinct', 0)} distinct signatures, "
            f"{reply.get('total', 0)} records in the ring"
        )
        for key, n in sorted(counts.items()):
            print(f"  {key}: {n:.0f}")
        for row in reply.get("errors", []):
            first = time.strftime("%H:%M:%S", time.localtime(row.get("first_ts", 0)))
            last = time.strftime("%H:%M:%S", time.localtime(row.get("last_ts", 0)))
            print(
                f"  x{row.get('count', 0):<5d} [{row.get('kind')}] "
                f"{row.get('exc_type', '?')} in {row.get('name', '?')} "
                f"(first {first}, last {last})"
            )
            msg = str(row.get("message", "")).splitlines()
            if msg:
                print(f"         {msg[0][:160]}")
        return 0
    rows = reply.get("summary", [])
    if not rows:
        print(
            f"no {args.what} flight records yet "
            "(is RAY_TPU_TASK_EVENTS=0, or nothing run?)"
        )
        return 0
    if args.what == "serve":
        _latency_table(rows, "deployment", "stage", "deployment", "stage")
        for dep, p in sorted(reply.get("ttft", {}).items()):
            print(
                f"TTFT {dep}: p50={p['p50'] * 1e3:.1f}ms "
                f"p99={p['p99'] * 1e3:.1f}ms (n={p['count']})"
            )
        for dep, p in sorted(reply.get("tpot", {}).items()):
            print(
                f"TPOT {dep}: p50={p['p50'] * 1e3:.2f}ms "
                f"p99={p['p99'] * 1e3:.2f}ms (n={p['count']})"
            )
        _print_engine_gauges(reply.get("engine", {}))
        _print_fleet_gauges(reply.get("fleet", {}))
    elif args.what == "train":
        _latency_table(rows, "run", "phase", "run", "phase")
        for run, st in sorted(reply.get("runs", {}).items()):
            mfu = st.get("mfu")
            print(
                f"run {run}: steps={st.get('steps', 0):.0f} "
                f"p50={st.get('p50_s', 0) * 1e3:.1f}ms "
                f"p99={st.get('p99_s', 0) * 1e3:.1f}ms "
                f"jitter={st.get('jitter_pct', 0):.1f}%"
                + (f" mfu={mfu:.3f}" if mfu is not None else "")
            )
    else:
        _latency_table(rows, "name", "phase", "task", "phase")
    print(f"({reply.get('total_records', 0)} records joined at the head)")
    return 0


def _write_folded(stacks, out_path):
    """Write a profile_api.collect() result as ONE merged collapsed-stack
    file (role;pid;thread roots keep per-process flames separable) and
    print the per-bucket totals + each bucket's hottest leaf frames."""
    from ray_tpu.util import profile_api

    with open(out_path, "w") as f:
        f.write(profile_api.folded_text(stacks))
    total = 0
    for bucket in sorted(stacks):
        per = stacks[bucket]
        n = sum(per.values())
        total += n
        top = sorted(per.items(), key=lambda kv: -kv[1])[:3]
        print(f"  {bucket:24s} {n:7d} samples, {len(per)} stacks")
        for folded, count in top:
            leaf = folded.rsplit(";", 1)[-1]
            print(f"      {count:6d}  {leaf}")
    print(f"wrote {total} samples to {out_path}")
    print("render with: flamegraph.pl " + out_path + " > profile.svg")


def cmd_profile(args):
    """`ray-tpu profile start|stop|snapshot|status`: the cluster-wide
    wall-clock sampling profiler (see util/profile_api.py)."""
    import ray_tpu
    from ray_tpu.util import profile_api

    ray_tpu.init(address=_read_address(args))
    roles = args.role or None
    if args.action == "start":
        st = profile_api.start(hz=args.hz, roles=roles, deep=args.deep)
        print(
            f"profiler armed (hz={st.get('ctrl', {}).get('hz', args.hz)}, "
            f"roles={roles or 'all'}, deep={args.deep})"
        )
        return 0
    if args.action == "status":
        st = profile_api.status()
        print(f"armed: {st.get('armed')}  ctrl: {st.get('ctrl')}")
        for bucket, agg in sorted((st.get("aggregate") or {}).items()):
            print(
                f"  {bucket:24s} samples={agg.get('samples', 0):7d} "
                f"stacks={agg.get('distinct_stacks', 0):5d} "
                f"overhead={agg.get('overhead_ratio', 0.0):.2%}"
            )
        return 0
    out = args.out or f"/tmp/ray-tpu-profile-{int(time.time())}.folded"
    if args.action == "stop":
        # disarm FIRST: the disarm-triggered final flush carries each
        # process's last partial window; collecting before it lands
        # would drop up to profiler_flush_period_s of samples
        profile_api.stop()
        time.sleep(1.0)
        stacks = profile_api.collect()
    else:  # snapshot
        stacks = profile_api.snapshot(
            duration=args.duration, hz=args.hz, roles=roles, deep=args.deep
        )
    if not stacks:
        print(
            "no samples collected (is the cluster idle, or was every "
            "process started with RAY_TPU_PROFILER=0?)"
        )
        return 1
    _write_folded(stacks, out)
    return 0


def cmd_stacks(args):
    """`ray-tpu stacks`: one-shot cluster-wide native stack dump over
    PROFILE_CTRL — every profiler-aware process ships all-thread
    tracebacks to the head."""
    import ray_tpu
    from ray_tpu.util import profile_api

    ray_tpu.init(address=_read_address(args))
    dumps = profile_api.stack_dumps()
    if not dumps:
        print("no stack dumps arrived (RAY_TPU_PROFILER=0 everywhere?)")
        return 1
    for d in dumps:
        print(f"##### {d.get('role')} pid={d.get('pid')} node={d.get('node')}")
        print(d.get("text", ""))
        print()
    print(f"({len(dumps)} process dumps)")
    return 0


def cmd_logs(args):
    """`ray-tpu logs --actor|--task|--replica|--job|--node|--worker ID`:
    pull-based log retrieval through the head's LOG_FETCH resolution —
    tail-N by default, ``--follow`` switches to cursor polling."""
    import ray_tpu
    from ray_tpu._private import log_plane
    from ray_tpu._private import worker as worker_mod

    ray_tpu.init(address=_read_address(args))
    cw = worker_mod._require_connected()
    kind = None
    ident = ""
    for k in ("actor", "task", "replica", "job", "node", "worker"):
        v = getattr(args, k, None)
        if v:
            kind, ident = k, v
            break
    if kind is None:
        print(
            "pick an entity: --actor/--task/--replica/--job/--node/--worker ID",
            file=sys.stderr,
        )
        return 2

    def _print(records):
        for rec in records:
            prefix = log_plane.record_prefix(rec, rec.get("src", ""))
            print(f"{prefix} {rec.get('msg', '')}", flush=True)

    reply = cw.fetch_log(
        {"kind": kind, "id": ident, "tail": args.tail, "grep": args.grep}
    )
    if not reply.get("ok"):
        print(f"log fetch failed: {reply.get('error')}", file=sys.stderr)
        return 1
    _print(reply.get("records") or [])
    if not args.follow:
        return 0
    cursor = reply.get("cursor") or {}
    try:
        while True:
            time.sleep(1.0)
            reply = cw.fetch_log(
                {"kind": kind, "id": ident, "cursor": cursor, "grep": args.grep}
            )
            if not reply.get("ok"):
                print(f"log follow failed: {reply.get('error')}", file=sys.stderr)
                return 1
            _print(reply.get("records") or [])
            cursor = reply.get("cursor") or cursor
    except KeyboardInterrupt:
        return 0


def cmd_slo(args):
    """`ray-tpu slo`: the watchdog's verdict per declared SLO."""
    import ray_tpu
    from ray_tpu.experimental.state import slo_status

    ray_tpu.init(address=_read_address(args))
    reply = slo_status()
    slos = reply.get("slos", [])
    if not slos:
        print(
            "no SLOs declared (ray_tpu.util.slo_api.set_slos([...]) or "
            "RAY_TPU_SLO_SPECS)"
        )
        return 0
    hdr = (
        f"{'slo':28s} {'ok':>4s} {'value':>12s} {'threshold':>12s} "
        f"{'burn':>8s} {'window':>8s} {'samples':>8s}"
    )
    print(hdr)
    print("-" * len(hdr))
    breached = 0
    for s in slos:
        ok = bool(s.get("ok"))
        breached += 0 if ok else 1
        val = s.get("value")
        print(
            f"{s['name'][:28]:28s} {'OK' if ok else 'FAIL':>4s} "
            f"{(f'{val:.4g}' if val is not None else '-'):>12s} "
            f"{s.get('threshold', 0):>12.4g} "
            f"{s.get('burn_rate', 0):>8.2f} "
            f"{s.get('window_s', 0):>7.0f}s "
            f"{s.get('samples', 0):>8d}"
        )
    return 1 if breached else 0


def main():
    parser = argparse.ArgumentParser(prog="ray-tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("start", help="start a head node")
    p.add_argument("--head", action="store_true")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--num-cpus", type=float, default=None)
    p.add_argument("--num-tpus", type=float, default=None)
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("stop", help="stop the head")
    p.set_defaults(fn=cmd_stop)

    for name, fn in (("status", cmd_status), ("memory", cmd_memory), ("metrics", cmd_metrics)):
        p = sub.add_parser(name)
        p.add_argument("--address", default=None)
        p.set_defaults(fn=fn)

    p = sub.add_parser("timeline", help="export a chrome://tracing JSON of recent tasks")
    p.add_argument("--address", default=None)
    p.add_argument("--output", "-o", default=None)
    p.set_defaults(fn=cmd_timeline)

    p = sub.add_parser("summary", help="workload summaries from the flight recorder")
    p.add_argument(
        "what",
        choices=["tasks", "serve", "train", "memory", "preemptions", "head", "errors"],
    )
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_summary)

    p = sub.add_parser(
        "logs", help="fetch logs by entity (worker/actor/task/replica/job/node)"
    )
    p.add_argument("--address", default=None)
    p.add_argument("--actor", default=None, help="actor id (hex, prefix ok)")
    p.add_argument("--task", default=None, help="task id (hex, prefix ok)")
    p.add_argument(
        "--replica", default=None, help="serve replica as deployment#index"
    )
    p.add_argument("--job", default=None, help="job id (hex)")
    p.add_argument("--node", default=None, help="node id (hex, prefix ok)")
    p.add_argument("--worker", default=None, help="worker id (hex, prefix ok)")
    p.add_argument("--tail", type=int, default=100, help="last N lines (default 100)")
    p.add_argument("--follow", "-f", action="store_true", help="keep polling for new lines")
    p.add_argument("--grep", default=None, help="only lines matching this regex")
    p.set_defaults(fn=cmd_logs)

    p = sub.add_parser("slo", help="SLO watchdog verdicts (exit 1 on a breach)")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_slo)

    p = sub.add_parser(
        "profile",
        help="cluster-wide sampling profiler (flamegraph collapsed stacks)",
    )
    p.add_argument("action", choices=["start", "stop", "snapshot", "status"])
    p.add_argument("--address", default=None)
    p.add_argument("--duration", type=float, default=2.0, help="snapshot window (s)")
    p.add_argument("--hz", type=int, default=None, help="sampling rate (default: profiler_hz config)")
    p.add_argument(
        "--role",
        action="append",
        default=None,
        help="only sample these roles (head/raylet/worker/driver/engine/dashboard); repeatable",
    )
    p.add_argument(
        "--deep",
        action="store_true",
        help="also collect jax.profiler device traces on RAY_TPU_PROFILER_DEVICE=1 workers",
    )
    p.add_argument("--out", "-o", default=None, help="collapsed-stack output file")
    p.set_defaults(fn=cmd_profile)

    p = sub.add_parser(
        "stacks", help="one-shot cluster-wide native stack dump (all threads)"
    )
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_stacks)

    p = sub.add_parser("submit", help="submit a job entrypoint command")
    p.add_argument("--address", default=None)
    p.add_argument("--wait", action="store_true")
    p.add_argument("--timeout", type=float, default=600)
    p.add_argument("entrypoint", nargs=argparse.REMAINDER)
    p.set_defaults(fn=cmd_submit)

    args = parser.parse_args()
    sys.exit(args.fn(args))


if __name__ == "__main__":
    main()
