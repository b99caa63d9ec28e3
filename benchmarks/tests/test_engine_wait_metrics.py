"""The four readers PR 38 adds over the engine thread's spans
(``engine_lock_wait_ms``, ``engine_emit_ms``, ``engine_sync_wait_share``,
``engine_gauge_tick_ms``) on hand-written planes with known answers and the
capture's ends cut, on traces without the new spans (a parent commit), and in
the tiny traced rehearsal of one serving cell, which must print all four."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import trace_reduce
from benchmarks.layer_metrics import engine_deliver_ms, engine_emit_ms, engine_gauge_tick_ms, engine_lock_wait_ms, engine_sync_wait_share

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
READERS = {
    "engine_lock_wait_ms": engine_lock_wait_ms,
    "engine_emit_ms": engine_emit_ms,
    "engine_sync_wait_share": engine_sync_wait_share,
    "engine_gauge_tick_ms": engine_gauge_tick_ms,
}
MS = 1e6  # the planes below are written in milliseconds


def ev(name, start_ms, end_ms):
    return (name, start_ms * MS, (end_ms - start_ms) * MS)


# the capture begins in the middle of a turn: what is left of its children counts in no turn,
# but a gauge tick is whole wherever it lies (0.2 ms)
CUT_HEAD = [ev("engine/deliver", 30, 39), ev("engine/lock", 30, 36), ev("engine/emit", 36.5, 39), ev("engine/gauges", 41, 41.2)]
# turn A, 100-160: decode only.  lock 0.1 + 0.2 + 1.5 = 1.8, emit 2, sync 50 of 60
TURN_A = [
    ev("engine/iteration", 100, 160), ev("engine/admit", 100, 101), ev("engine/lock", 100.2, 100.3), ev("engine/lock", 101, 101.2),
    ev("engine/decode", 101.5, 158), ev("engine/build", 101.5, 102), ev("engine/dispatch", 102, 103),
    ev("engine/sync", 103, 153), ev("engine/deliver", 153, 157), ev("engine/lock", 153, 154.5), ev("engine/emit", 155, 157),
    ev("engine/sync", 158, 158.0), ev("engine/gauges", 158.5, 158.9),
]
# an idle turn between them ticks too (0.6 ms); its counter read waits for nothing and is in no turn
BETWEEN = [ev("engine/idle", 160, 170), ev("engine/sync", 170, 170.5), ev("engine/gauges", 170.5, 171.1), ev("engine/idle", 171.1, 180)]
# turn B, 180-270: a chunk, the decode step, the step's delivery and the first token's.
# lock 0.2 + 0.2 + 0.3 + 3 = 3.7 (a stalled delivery), emit 1 + 0.5 = 1.5, sync 60 + 10 of 90
TURN_B = [
    ev("engine/iteration", 180, 270), ev("engine/admit", 180, 181), ev("engine/lock", 180.1, 180.3), ev("engine/lock", 181, 181.2),
    ev("engine/prefill", 181.5, 190), ev("engine/build", 181.5, 182), ev("engine/dispatch", 182, 184),
    ev("engine/decode", 190, 268), ev("engine/build", 190, 191), ev("engine/dispatch", 191, 192),
    ev("engine/sync", 192, 252), ev("engine/deliver", 252, 254), ev("engine/lock", 252, 252.3), ev("engine/emit", 253, 254),
    ev("engine/sync", 254, 264), ev("engine/deliver", 264, 268), ev("engine/lock", 264, 267), ev("engine/emit", 267.5, 268),
]
# and it ends in the middle of one
CUT_TAIL = [ev("engine/admit", 280, 281), ev("engine/lock", 280, 280.9), ev("engine/gauges", 275, 275.3)]
WHOLE = CUT_HEAD + TURN_A + BETWEEN + TURN_B + CUT_TAIL


def view_of(host_events):
    planes = [
        ("/device:TPU:0", [(trace_reduce.OPS_LINE, [ev("fusion.1", 60, 95), ev("fusion.1", 104, 150)])]),
        ("/host:CPU", [("python3", sorted(host_events, key=lambda e: e[1])), ("other", [ev("$threading.py:1 wait", 0, 300)])]),
    ]
    return {"planes": planes, "trace": trace_reduce.reduce_trace(planes)}


@pytest.mark.parametrize("name,expected", [
    ("engine_lock_wait_ms", (1.8 + 3.7) / 2),
    ("engine_emit_ms", (2.0 + 1.5) / 2),
    ("engine_sync_wait_share", 100.0 * (50 + 70) / (60 + 90)),
    ("engine_gauge_tick_ms", (0.2 + 0.4 + 0.6 + 0.3) / 4),
])
def test_known_answers_with_the_ends_cut(name, expected):
    assert READERS[name].read(view_of(WHOLE)) == pytest.approx(expected, rel=1e-9)


def test_lock_and_emit_split_a_delivery():
    # what is left of engine/deliver after the two is the bookkeeping under the lock
    view = view_of(WHOLE)
    deliver = engine_deliver_ms.read(view)
    assert deliver == pytest.approx((4.0 + 2.0 + 4.0) / 2)
    in_deliveries = (1.5 + 0.3 + 3.0) / 2  # the acquisitions inside deliveries alone
    assert deliver - in_deliveries - engine_emit_ms.read(view) == pytest.approx((0.5 + 0.7 + 0.5) / 2)


def without(events, *names):
    return [e for e in events if e[0] not in names]


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_trace_from_before_the_new_spans(name):
    # a parent commit writes engine/iteration, sync, deliver and gauges, and neither lock nor emit
    got = READERS[name].read(view_of(without(WHOLE, "engine/lock", "engine/emit")))
    if name in ("engine_lock_wait_ms", "engine_emit_ms"):
        assert got is None
    else:
        assert got == pytest.approx(READERS[name].read(view_of(WHOLE)))
    # no engine span at all, or children alone (every turn cut): no turn, so nothing but a whole tick
    host = [ev("PjitFunction(decode_step_paged)", 102, 103), ev("$loop.py:372 _decode_step", 101, 158)]
    assert READERS[name].read(view_of(host)) is None
    cut = READERS[name].read(view_of(CUT_HEAD + CUT_TAIL))
    assert cut == pytest.approx(0.25) if name == "engine_gauge_tick_ms" else cut is None


def test_turns_that_never_waited_read_zero_not_none():
    # the spans are there and empty: a number, so that "no wait" and "no span" stay apart
    quiet = [ev("engine/iteration", 100, 160), ev("engine/lock", 100, 100), ev("engine/deliver", 150, 151), ev("engine/emit", 150, 150)]
    view = view_of(quiet)
    assert engine_lock_wait_ms.read(view) == 0.0 and engine_emit_ms.read(view) == 0.0
    assert engine_sync_wait_share.read(view) == 0.0  # a turn that never waited for the device: the host sets the pace
    assert engine_gauge_tick_ms.read(view) is None  # no tick in the capture


def test_the_tiny_traced_rehearsal_prints_all_four():
    cell = "mistral-7b-l16.chat"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"] if cell in m.get("workloads", [cell]) and m["name"].partition(".")[0] in READERS}
    assert listed == {f"{name}.chat" for name in READERS}
    for m in bench["per_layer"]:  # twenty entries: each reader under the five suffixes, one cell each
        if m["name"].partition(".")[0] in READERS:
            assert (m["source"], m["layer"], len(m["workloads"])) == ("device_trace", "Engine", 1), m
    assert sum(m["name"].partition(".")[0] in READERS for m in bench["per_layer"]) == 20
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", cell, "--seed", "3800000001", "--seconds", "5", "--trace", "1", "--tiny"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert listed <= set(out["metrics"]), listed - set(out["metrics"])
    values = {name: out["metrics"][f"{name}.chat"]["value"] for name in READERS}
    assert all(v >= 0.0 for v in values.values()), values
    assert 0.0 < values["engine_sync_wait_share"] < 100.0
    # the gauges' publisher is another thread: what is left of a tick on the engine thread is no round trip to the head
    assert values["engine_gauge_tick_ms"] < 1.0, values
    assert values["engine_emit_ms"] <= out["metrics"]["engine_deliver_ms.chat"]["value"]
