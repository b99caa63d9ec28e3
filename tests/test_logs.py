"""Cluster log plane v2 (util/OBSERVABILITY.md "Logs"): structured
capture, job-scoped streaming, LOG_FETCH retrieval, error aggregation,
crash forensics.

The acceptance contract these tests pin down:

- every captured line is ONE structured record (sentinel + JSON) carrying
  the running-task identity (job/node/pid/wid/actor/task/stream),
- two concurrent drivers each see ONLY their own job's worker lines
  (asserted in both directions),
- `LOG_FETCH` resolves an entity (worker / actor / serve replica / task /
  job / node) to files on nodes and tails/follows across the rotation
  seam — including actors on a remote node,
- an uncaught task exception ships a structured error record to the
  head's signature-deduped ring AND carries the victim's last-K log
  lines inside the `RayTaskError` seen at `ray_tpu.get`; an actor death
  carries its tail inside `RayActorError`,
- the driver sink collapses repeated lines and rate-caps floods,
- `RAY_TPU_LOG_STRUCTURED=0` falls back to raw lines, byte-for-byte
  stamp-free (same convention as RAY_TPU_TASK_EVENTS=0),
- structured capture costs ≤5% on the tracked ray_perf task-batch pair.
"""

import os
import subprocess
import sys
import textwrap
import time

import pytest

import ray_tpu
from ray_tpu._private import log_plane
from ray_tpu._private.log_monitor import (
    DriverLogSink,
    LogTailer,
    read_new_records,
    tail_file_records,
)
from ray_tpu.exceptions import RayActorError, RayTaskError

pytestmark = pytest.mark.logs


# ---------------------------------------------------------------------------
# structured-record unit
# ---------------------------------------------------------------------------


def test_record_roundtrip_and_parse():
    """encode_record/parse_line round-trip; raw lines and sentinel-
    prefixed garbage both come back None (stamp-free path)."""
    rec = log_plane.make_record("out", "hello world")
    line = log_plane.encode_record(rec)
    assert line.startswith(log_plane.SENTINEL) and line.endswith("\n")
    back = log_plane.parse_line(line.rstrip("\n"))
    assert back is not None
    assert back["msg"] == "hello world" and back["stream"] == "out"
    assert isinstance(back["ts"], float)
    assert log_plane.parse_line("a plain raw line") is None
    assert log_plane.parse_line(log_plane.SENTINEL + "{not json") is None
    assert log_plane.parse_line(log_plane.SENTINEL + '["no msg"]') is None


def test_task_context_merges_and_clears():
    """The per-line stamp is _static + _task merged; the task dict is
    swapped wholesale at task boundaries."""
    log_plane.set_static(node="n0def0", pid=1234)
    try:
        log_plane.task_context(
            task="t" * 8, trace="tr1", job="j" * 8, actor="a" * 8, cls="Cls"
        )
        rec = log_plane.make_record("err", "x")
        assert rec["node"] == "n0def0" and rec["pid"] == 1234
        assert rec["task"] == "t" * 8 and rec["trace"] == "tr1"
        assert rec["job"] == "j" * 8 and rec["actor"] == "a" * 8
        assert rec["cls"] == "Cls"
        log_plane.clear_task_context()
        rec2 = log_plane.make_record("err", "y")
        assert "task" not in rec2 and "actor" not in rec2
        assert rec2["node"] == "n0def0"  # static survives the task end
    finally:
        log_plane.set_static(node=None, pid=None)
        log_plane.clear_task_context()


def test_structured_stream_wraps_lines():
    """Completed lines become records; partial writes buffer; a line
    that is already a record passes through un-double-wrapped."""
    import io

    raw = io.StringIO()
    s = log_plane.StructuredStream(raw, "out")
    s.write("par")
    assert raw.getvalue() == ""  # no newline yet: nothing lands
    s.write("tial\nsecond line\n")
    lines = [ln for ln in raw.getvalue().split("\n") if ln]
    recs = [log_plane.parse_line(ln) for ln in lines]
    assert [r["msg"] for r in recs] == ["partial", "second line"]
    assert all(r["stream"] == "out" for r in recs)
    # nested-wrap guard: an incoming record line is NOT stamped again
    pre = log_plane.encode_record({"ts": 1.0, "msg": "inner", "stream": "err"})
    s.write(pre)
    assert raw.getvalue().count(log_plane.SENTINEL) == 3
    inner = log_plane.parse_line(raw.getvalue().split("\n")[2])
    assert inner["msg"] == "inner" and inner["stream"] == "err"


def test_driver_tee_preserves_terminal_bytes():
    """Tee mode: the terminal sees EVERY byte unchanged (partials
    included — progress bars); the tee file gets records for completed
    lines only."""
    import io

    term, tee = io.StringIO(), io.StringIO()
    s = log_plane.StructuredStream(term, "out", emit_to=tee)
    s.write("progress: 10%\rprogress: 20%")  # no newline: partial
    s.write("\ndone\n")
    assert term.getvalue() == "progress: 10%\rprogress: 20%\ndone\n"
    recs = [
        log_plane.parse_line(ln) for ln in tee.getvalue().split("\n") if ln
    ]
    assert [r["msg"] for r in recs] == ["progress: 10%\rprogress: 20%", "done"]


def test_record_prefix_forms():
    """The (ClassName pid=… node=…) driver prefix degrades gracefully."""
    assert (
        log_plane.record_prefix({"cls": "Counter", "pid": 7, "node": "ab12"})
        == "(Counter pid=7 node=ab12)"
    )
    assert (
        log_plane.record_prefix({"wid": "w1", "pid": 7, "node": "ab12"})
        == "(worker pid=7 node=ab12)"
    )
    assert log_plane.record_prefix({"pid": 9, "node": "cd"}) == "(pid=9 node=cd)"
    assert log_plane.record_prefix({}, "worker-head-0.log") == "(worker-head-0.log)"
    assert log_plane.record_prefix({}) == "(?)"


# ---------------------------------------------------------------------------
# tailer: truncation blindness fix + rotation
# ---------------------------------------------------------------------------


def _mk_tailer(tmp_path, published, **kw):
    return LogTailer(
        str(tmp_path), published.append, pattern="worker-*.log", poll_s=999, **kw
    )


def test_tailer_truncation_resets_offset(tmp_path):
    """Satellite 1: a file that shrank under the tailer (rotation, `>`
    truncation) restarts from 0 instead of silently reading nothing
    forever; the stale partial-line buffer is dropped with it."""
    path = tmp_path / "worker-x-0.log"
    published = []
    t = _mk_tailer(tmp_path, published)
    path.write_text("first\nsecond\npart")  # trailing partial line
    t.scan_once()
    assert published[-1]["lines"] == ["first", "second"]
    assert t._partial[str(path)] == b"part"
    # truncate + rewrite smaller: v1 kept offset 17 > size and went blind
    path.write_text("fresh\n")
    t.scan_once()
    assert published[-1]["lines"] == ["fresh"]
    assert t._offsets[str(path)] == 6
    assert str(path) not in t._partial  # stale partial belongs to dead bytes


def test_tailer_rotation_and_seam_read(tmp_path):
    """Satellite 2: the tailer copytruncates a file past the size cap;
    tail_file_records reads across the `.1` seam as one stream and the
    follow cursor picks up post-rotation appends."""
    path = tmp_path / "worker-y-0.log"
    published = []
    t = _mk_tailer(tmp_path, published, rotation_bytes=64, rotation_backups=2)
    old = [log_plane.encode_record({"ts": float(i), "msg": f"old-{i}"}) for i in range(8)]
    path.write_text("".join(old))  # > 64 bytes: rotates on this scan
    t.scan_once()
    assert os.path.exists(f"{path}.1") and os.path.getsize(path) == 0
    assert published[-1]["lines"] == [f"old-{i}" for i in range(8)]
    # post-rotation appends land in the (truncated) live file
    with open(path, "a") as f:
        f.write(log_plane.encode_record({"ts": 9.0, "msg": "new-0"}))
    recs, cursor = tail_file_records([str(path)], tail=100)
    assert [r["msg"] for r in recs] == [f"old-{i}" for i in range(8)] + ["new-0"]
    assert cursor[str(path)] == os.path.getsize(path)
    # tail-N trims from the old end of the seam, not the new
    recs2, _ = tail_file_records([str(path)], tail=3)
    assert [r["msg"] for r in recs2] == ["old-7", "new-0"][-3:] or [
        r["msg"] for r in recs2
    ] == ["old-6", "old-7", "new-0"]
    # follow: only bytes appended past the cursor come back
    with open(path, "a") as f:
        f.write(log_plane.encode_record({"ts": 10.0, "msg": "new-1"}))
        f.write(log_plane.SENTINEL + '{"ts":11.0,"msg":"new-')  # incomplete line
    recs3, cursor2 = read_new_records(cursor)
    assert [r["msg"] for r in recs3] == ["new-1"]
    # the partial line did NOT advance the cursor — re-read whole next poll
    with open(path, "a") as f:
        f.write('2"}\n')
    recs4, _ = read_new_records(cursor2)
    assert [r["msg"] for r in recs4] == ["new-2"]


def test_tail_filters_grep_and_job(tmp_path):
    """Read-side filters: grep matches the message text, job keeps
    records of that job plus unstamped raw lines."""
    path = tmp_path / "worker-z-0.log"
    with open(path, "w") as f:
        f.write(log_plane.encode_record({"ts": 1.0, "msg": "alpha one", "job": "j1"}))
        f.write(log_plane.encode_record({"ts": 2.0, "msg": "alpha two", "job": "j2"}))
        f.write("raw alpha line\n")
        f.write(log_plane.encode_record({"ts": 3.0, "msg": "beta", "job": "j1"}))
    recs, _ = tail_file_records([str(path)], tail=100, grep="alpha")
    assert [r["msg"] for r in recs] == ["alpha one", "alpha two", "raw alpha line"]
    recs, _ = tail_file_records([str(path)], tail=100, job="j1")
    assert [r["msg"] for r in recs] == ["alpha one", "raw alpha line", "beta"]


# ---------------------------------------------------------------------------
# driver sink: flood control
# ---------------------------------------------------------------------------


def test_driver_sink_collapses_repeats():
    """Satellite 3a: a run of identical lines prints once, then one
    `… repeated N×` line when the run breaks."""
    out = []
    sink = DriverLogSink(write=out.append, rate_lines_s=1000)
    for _ in range(50):
        sink.feed({"source": "w0", "lines": ["same line"]})
    sink.feed({"source": "w0", "lines": ["different"]})
    assert out == ["(w0) same line", "… repeated 50×", "(w0) different"]
    # flush surfaces a pending run at shutdown
    for _ in range(3):
        sink.feed({"source": "w0", "lines": ["different"]})
    sink.flush()
    assert out[-1] == "… repeated 4×"


def test_driver_sink_rate_cap():
    """Satellite 3b: sustained distinct-line floods hit the per-source
    token bucket; the excess drops with one suppression notice when the
    flood subsides."""
    clock = [0.0]
    out = []
    sink = DriverLogSink(write=out.append, rate_lines_s=10, now=lambda: clock[0])
    for i in range(100):  # burst capacity is 2×rate = 20 tokens
        sink.feed({"source": "w0", "lines": [f"line-{i}"]})
    assert len(out) == 20
    assert all(f"line-{i}" in out[i] for i in range(20))
    clock[0] += 1.0  # refill 10 tokens
    sink.feed({"source": "w0", "lines": ["after flood"]})
    assert out[-2] == "… 80 line(s) suppressed (rate limit) …"
    assert out[-1] == "(w0) after flood"
    # per-source isolation: a quiet source is never taxed by a noisy one
    sink.feed({"source": "w1", "lines": ["quiet"]})
    assert out[-1] == "(w1) quiet"


# ---------------------------------------------------------------------------
# live cluster: capture, retrieval, errors
# ---------------------------------------------------------------------------


def _wait_for(pred, timeout, interval=0.25):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


def test_worker_lines_stamped_and_fetchable(shutdown_only):
    """print() inside a task arrives at the driver prefixed with worker
    identity, and the same line is retrievable after the fact by job and
    by node through LOG_FETCH; list_logs sees the worker's file."""
    from ray_tpu._private.worker import global_worker
    from ray_tpu.experimental.state import get_log, list_logs

    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    def chatty():
        print("stamped-marker-7501")
        return os.getpid()

    pid = ray_tpu.get(chatty.remote(), timeout=120)
    assert _wait_for(
        lambda: any("stamped-marker-7501" in l for l in global_worker.captured_logs),
        20,
    ), "worker line never streamed to the driver"
    cw = global_worker.core_worker
    job_hex = cw.job_id.binary().hex()
    # by job: the record rides with its stamp (the prefix carries the pid)
    lines = get_log(job_id=job_hex, tail=200, grep="stamped-marker-7501")
    assert any("stamped-marker-7501" in l and f"pid={pid}" in l for l in lines), lines
    # by node: head node resolves through the head-local agent
    node_hex = ray_tpu.nodes()[0]["NodeID"]
    lines = get_log(node_id=node_hex, tail=400, grep="stamped-marker-7501")
    assert any("stamped-marker-7501" in l for l in lines)
    files = list_logs()
    assert files and any(":worker-" in f for f in files)


def test_actor_logs_cross_node_tail_and_follow(shutdown_only):
    """An actor pinned to a REMOTE node is addressable by actor id:
    tail-N returns only ITS lines, and a cursor follow sees lines printed
    after the first fetch (raylet-side log agent, head-routed)."""
    from ray_tpu._private.worker import global_worker
    from ray_tpu.cluster_utils import Cluster

    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    try:
        ray_tpu.init(address=c.address)
        c.add_node(num_cpus=2, resources={"far": 1.0})

        @ray_tpu.remote(resources={"far": 0.5})
        class Talker:
            def say(self, what):
                print(f"talker-says-{what}")
                return os.getpid()

        a = Talker.remote()
        ray_tpu.get(a.say.remote("first"), timeout=120)
        aid = a._actor_id.hex()
        cw = global_worker.core_worker
        reply = cw.fetch_log({"kind": "actor", "id": aid, "tail": 50})
        assert reply["ok"], reply
        msgs = [r["msg"] for r in reply["records"]]
        assert "talker-says-first" in msgs, msgs
        # every returned record is stamped with THIS actor (tail-N is
        # entity-scoped, not file-scoped)
        assert all(r.get("actor", "").startswith(aid) for r in reply["records"])
        assert all(r.get("cls") == "Talker" for r in reply["records"])
        # follow: the reply cursor sees only what lands after it
        cursor = reply["cursor"]
        assert cursor, "tail reply must carry a follow cursor"
        ray_tpu.get(a.say.remote("second"), timeout=120)
        got = []

        def _poll():
            nonlocal cursor
            r = cw.fetch_log({"kind": "actor", "id": aid, "cursor": cursor})
            assert r["ok"], r
            got.extend(rec["msg"] for rec in r["records"])
            cursor = r["cursor"] or cursor
            return any("talker-says-second" in m for m in got)

        assert _wait_for(_poll, 30), got
        assert not any("talker-says-first" in m for m in got), (
            "follow replayed lines from before the cursor"
        )
    finally:
        c.shutdown()


def test_serve_replica_logs_by_deployment_index(shutdown_only):
    """A serve replica is addressable as `deployment#index` without
    knowing its actor id (the controller's SERVE_REPLICA naming
    contract)."""
    from ray_tpu import serve
    from ray_tpu.experimental.state import get_log

    ray_tpu.init(num_cpus=4)

    @serve.deployment(name="logdep")
    class LogDep:
        def __call__(self, x):
            print(f"replica-handled-{x}")
            return x * 2

    handle = serve.run(LogDep.bind())
    assert ray_tpu.get(handle.remote(21), timeout=120) == 42
    lines = []

    def _fetch():
        nonlocal lines
        lines = get_log(replica="logdep#0", tail=100)
        return any("replica-handled-21" in l for l in lines)

    # on a loaded box the fetch can win the race against the replica's
    # record reaching its log file: poll, don't single-shot
    assert _wait_for(_fetch, 60), lines
    # stamped with the hosting actor class (the serve Replica wrapper)
    assert any("(Replica pid=" in l for l in lines), lines


def test_task_error_ships_log_tail_and_dedupes(shutdown_only):
    """Crash forensics e2e: a task that prints then raises surfaces its
    last-K log lines inside the RayTaskError at ray_tpu.get, and the
    head's error ring dedupes repeats of the same signature."""
    from ray_tpu.experimental.state import summarize_errors

    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote(max_retries=0)
    def doomed(n):
        print(f"clue-before-crash-{n}")
        raise ValueError("doomed by design")

    with pytest.raises(RayTaskError) as ei:
        ray_tpu.get(doomed.remote(0), timeout=120)
    err = ei.value
    assert any("clue-before-crash-0" in ln for ln in err.log_tail), err.log_tail
    assert "clue-before-crash-0" in str(err)  # forensics visible in the message
    assert "doomed by design" in str(err)

    def _summary():
        s = summarize_errors()
        rows = [r for r in s["errors"] if r["exc_type"] == "ValueError"]
        return rows[0] if rows else None

    assert _wait_for(lambda: _summary() is not None, 20)
    first = _summary()
    assert first["count"] >= 1 and first["kind"] == "task"
    assert "doomed by design" in first["message"]
    # same signature again: count climbs, no new distinct group appears
    distinct_before = summarize_errors()["distinct"]
    with pytest.raises(RayTaskError):
        ray_tpu.get(doomed.remote(1), timeout=120)
    assert _wait_for(lambda: (_summary() or {}).get("count", 0) >= 2, 20)
    after = summarize_errors()
    assert after["distinct"] == distinct_before, "repeat signature split the group"
    assert any(
        k.startswith("kind=") and v >= 2 for k, v in after["counts"].items()
    ), after["counts"]


def test_actor_died_error_carries_log_tail(shutdown_only):
    """An actor hard-killed mid-call seals its pending calls with a
    RayActorError carrying the victim's recent log lines (the head's
    per-source forensics ring, snapshotted at death)."""
    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote(max_restarts=0)
    class Victim:
        def note(self):
            print("victim-last-words-9313")
            return "ok"

        def crash(self):
            os._exit(1)

    v = Victim.remote()
    assert ray_tpu.get(v.note.remote(), timeout=120) == "ok"
    # the head learns the line through the tailer (0.5s poll): give the
    # forensics ring time to hold it before the death snapshot
    time.sleep(2.0)
    # the in-flight crash call itself may seal client-side as a plain
    # connection-loss error; the head-sealed forensics ride on every
    # call that hits the dead actor AFTER the death is recorded
    with pytest.raises((RayActorError, RayTaskError)):
        ray_tpu.get(v.crash.remote(), timeout=120)
    deadline = time.time() + 60
    last = ""
    while True:
        try:
            ray_tpu.get(v.note.remote(), timeout=30)
            assert time.time() < deadline, "dead actor kept answering"
            time.sleep(0.5)
        except RayActorError as e:
            if "victim-last-words-9313" in str(e):
                break
            last = str(e)
            assert time.time() < deadline, f"seal carried no tail: {last}"
            time.sleep(0.5)
        except RayTaskError as e:
            # a retry racing the head's death record can still seal
            # client-side as a connection-loss RayTaskError on a slow
            # box; keep asking until the head-sealed forensics appear
            last = str(e)
            assert time.time() < deadline, f"no head seal, last: {last}"
            time.sleep(0.5)


def test_two_drivers_see_only_their_own_job(shutdown_only, tmp_path):
    """Job-scoped streaming, asserted in BOTH directions: two concurrent
    drivers on one cluster each receive only their own workers' lines.
    The second driver is a real subprocess connecting by address."""
    from ray_tpu._private.worker import global_worker

    ray_tpu.init(num_cpus=4)
    address = global_worker.address
    ready = tmp_path / "second-ready"
    done = tmp_path / "first-done"
    script = textwrap.dedent(
        f"""
        import os, time
        import ray_tpu
        from ray_tpu._private.worker import global_worker

        ray_tpu.init(address="{address}")

        @ray_tpu.remote
        def chatty():
            for _ in range(3):
                print("MARKER-SECOND-4186")
            return 1

        assert ray_tpu.get(chatty.remote(), timeout=120) == 1
        deadline = time.time() + 30
        while time.time() < deadline:
            if any("MARKER-SECOND-4186" in l for l in global_worker.captured_logs):
                break
            time.sleep(0.25)
        assert any("MARKER-SECOND-4186" in l for l in global_worker.captured_logs), (
            "second driver never saw its own worker lines"
        )
        open({str(ready)!r}, "w").close()
        # stay subscribed while the FIRST driver's job prints, then assert
        # none of its lines leaked into this job's stream
        deadline = time.time() + 60
        while time.time() < deadline and not os.path.exists({str(done)!r}):
            time.sleep(0.25)
        assert os.path.exists({str(done)!r}), "first driver never signalled"
        time.sleep(1.5)  # drain any in-flight pubsub deliveries
        leaked = [l for l in global_worker.captured_logs if "MARKER-FIRST-2954" in l]
        assert not leaked, f"cross-job leak into second driver: {{leaked}}"
        print("SECOND-DRIVER-OK")
        """
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-c", script],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        text=True,
    )
    try:
        assert _wait_for(ready.exists, 120), "second driver never came up"

        @ray_tpu.remote
        def chatty():
            for _ in range(3):
                print("MARKER-FIRST-2954")
            return 1

        assert ray_tpu.get(chatty.remote(), timeout=120) == 1
        assert _wait_for(
            lambda: any(
                "MARKER-FIRST-2954" in l for l in global_worker.captured_logs
            ),
            30,
        ), "first driver never saw its own worker lines"
        done.touch()
        out, errout = proc.communicate(timeout=120)
        assert proc.returncode == 0, f"second driver failed:\n{errout[-3000:]}"
        assert "SECOND-DRIVER-OK" in out
        # direction 2: the second job's lines (produced while THIS driver
        # was subscribed) never reached this driver's stream
        leaked = [
            l for l in global_worker.captured_logs if "MARKER-SECOND-4186" in l
        ]
        assert not leaked, f"cross-job leak into first driver: {leaked}"
    finally:
        if proc.poll() is None:
            proc.kill()


def test_structured_disabled_raw_fallback():
    """RAY_TPU_LOG_STRUCTURED=0 contract: the whole cluster writes raw
    lines — driver streaming still works, and NO log file anywhere in the
    session dir carries a single sentinel byte."""
    script = textwrap.dedent(
        """
        import glob, os, time
        import ray_tpu
        from ray_tpu._private import log_plane
        from ray_tpu._private.worker import global_worker

        assert not log_plane.enabled
        assert log_plane.install() is False  # hard no-op when disabled
        ray_tpu.init(num_cpus=2)

        @ray_tpu.remote
        def chatty():
            print("raw-mode-marker-6120")
            return 1

        assert ray_tpu.get(chatty.remote(), timeout=120) == 1
        # v1 behavior intact: the line still streams to the driver
        deadline = time.time() + 30
        while time.time() < deadline:
            if any("raw-mode-marker-6120" in l for l in global_worker.captured_logs):
                break
            time.sleep(0.25)
        assert any("raw-mode-marker-6120" in l for l in global_worker.captured_logs)
        session = global_worker.session_dir
        paths = glob.glob(os.path.join(session, "*.log*"))
        assert paths, f"no log files under {session}"
        joined = b"".join(open(p, "rb").read() for p in paths)
        assert b"raw-mode-marker-6120" in joined
        assert b"\\x1e" not in joined, "sentinel bytes leaked on the =0 path"
        print("RAW-FALLBACK-OK")
        """
    )
    env = dict(os.environ)
    env["RAY_TPU_LOG_STRUCTURED"] = "0"
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", script],
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, f"=0 driver failed:\n{proc.stderr[-3000:]}"
    assert "RAW-FALLBACK-OK" in proc.stdout


# ---------------------------------------------------------------------------
# overhead contract
# ---------------------------------------------------------------------------


def _task_pair_rate(tiny, seconds=0.8):
    """The tracked `tasks async batch 100`-shaped pair from ray_perf:
    batched .remote() bursts drained with one get."""
    t0 = time.perf_counter()
    done = 0
    while time.perf_counter() - t0 < seconds:
        ray_tpu.get([tiny.remote(i) for i in range(50)], timeout=60)
        done += 50
    return done / (time.perf_counter() - t0)


def test_overhead_bound_on_tracked_pair(monkeypatch, shutdown_only):
    """The ≤5% contract on the tracked ray_perf task-batch pair: a
    cluster with structured capture on is within 5% of one booted with
    RAY_TPU_LOG_STRUCTURED=0 (the stamp path is one dict swap per task
    and one merge per printed line — these tasks print nothing, so the
    cost is the swap).  Best-of trials absorb box noise; one full
    re-measure before failing so a scheduler hiccup can't flake CI."""
    from ray_tpu._private.config import RayConfig

    def measure(structured: bool):
        if structured:
            monkeypatch.delenv("RAY_TPU_LOG_STRUCTURED", raising=False)
        else:
            monkeypatch.setenv("RAY_TPU_LOG_STRUCTURED", "0")
        ray_tpu.init(num_cpus=2)

        @ray_tpu.remote
        def tiny(i):
            return i

        _task_pair_rate(tiny, seconds=1.0)  # warm pool + leases
        best = max(_task_pair_rate(tiny) for _ in range(3))
        ray_tpu.shutdown()
        RayConfig.reset()
        return best

    def compare():
        off = measure(structured=False)
        on = measure(structured=True)
        return on, off

    on, off = compare()
    if on < 0.95 * off:
        on, off = compare()  # one re-measure: noise, not policy
    assert on >= 0.95 * off, (
        f"structured capture cost {1 - on / off:.1%} "
        f"({on:.0f}/s on vs {off:.0f}/s off) breaks the ≤5% contract"
    )
