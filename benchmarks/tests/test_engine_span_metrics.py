"""The readers of the engine thread's spans (layer_metrics/_engine_spans.py
and the five metrics on it) on hand-written planes with known answers, and
the tiny traced rehearsal of the two serving cells, which must print every
one of them."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import trace_reduce
from benchmarks.layer_metrics import (
    _engine_spans,
    device_idle_in_host_turn,
    engine_deliver_ms,
    engine_gauges_ms_per_s,
    engine_host_turn_ms,
    engine_iter_prefill_ms,
)

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
READERS = {
    "engine_host_turn_ms": engine_host_turn_ms,
    "engine_iter_prefill_ms": engine_iter_prefill_ms,
    "engine_deliver_ms": engine_deliver_ms,
    "engine_gauges_ms_per_s": engine_gauges_ms_per_s,
    "device_idle_in_host_turn": device_idle_in_host_turn,
}
MS = 1e6  # the planes below are written in milliseconds


def ev(name, start_ms, end_ms):
    return (name, start_ms * MS, (end_ms - start_ms) * MS)


# the capture begins in the middle of a turn: its engine/iteration is not in
# the file, what is left of its children is
CUT_HEAD = [ev("engine/decode", 0, 40), ev("engine/sync", 5, 35), ev("engine/deliver", 36, 39), ev("engine/gauges", 41, 43)]
# turn A, 100-160: decode only.  own work 60 - 50 = 10, deliver 4, gauges 1.5
TURN_A = [
    ev("engine/iteration", 100, 160), ev("engine/admit", 100, 101), ev("engine/decode", 101, 158),
    ev("engine/build", 101, 102), ev("engine/dispatch", 102, 103), ev("PjitFunction(decode_step_paged)", 102.1, 102.9),
    ev("engine/sync", 103, 153), ev("engine/deliver", 153, 157), ev("engine/gauges", 158, 159.5),
]
# an idle turn between them publishes gauges too (1 ms)
BETWEEN = [ev("engine/idle", 160, 170), ev("engine/gauges", 170, 171), ev("engine/idle", 171, 180)]
# turn B, 180-270: a prefill chunk, then the decode step.  own work 90 - 70 = 20, deliver 4
TURN_B = [
    ev("engine/iteration", 180, 270), ev("engine/admit", 180, 181), ev("engine/prefill", 181, 190),
    ev("engine/build", 181, 182), ev("engine/dispatch", 182, 184), ev("engine/decode", 190, 268),
    ev("engine/build", 190, 191), ev("engine/dispatch", 191, 192), ev("engine/sync", 192, 262), ev("engine/deliver", 262, 266),
]
# and it ends in the middle of one
CUT_TAIL = [ev("engine/gauges", 275, 276), ev("engine/admit", 280, 281), ev("engine/build", 282, 283)]
# busy 60-95, 104-150, 184-189, 193-260, 285-290.  Idle inside the whole turns:
# 100-104, 150-160, 180-184, 189-193, 260-270 = 32 ms, of which under engine/sync
# 103-104, 150-153, 192-193, 260-262 = 7 ms
DEVICE = [ev("fusion.1", 60, 95), ev("fusion.1", 104, 150), ev("fusion.2", 184, 189), ev("fusion.1", 193, 260), ev("fusion.1", 285, 290)]


def view_of(host_events, device=DEVICE):
    planes = [
        ("/device:TPU:0", [(trace_reduce.OPS_LINE, list(device))]),
        ("/host:CPU", [("python3", sorted(host_events, key=lambda e: e[1])), ("other", [ev("$threading.py:1 wait", 0, 300)])]),
    ]
    return {"planes": planes, "trace": trace_reduce.reduce_trace(planes)}


WHOLE = CUT_HEAD + TURN_A + BETWEEN + TURN_B + CUT_TAIL


@pytest.mark.parametrize("name,expected", [
    ("engine_host_turn_ms", (10.0 + 20.0) / 2),
    ("engine_iter_prefill_ms", 90.0),
    ("engine_deliver_ms", (4.0 + 4.0) / 2),
    ("engine_gauges_ms_per_s", (1.5 + 1.0) / 0.170),
    ("device_idle_in_host_turn", 100.0 * (32 - 7) / 32),
])
def test_known_answers_with_the_ends_cut(name, expected):
    assert READERS[name].read(view_of(WHOLE)) == pytest.approx(expected, rel=1e-9)


def test_cut_turns_are_left_out():
    turns = _engine_spans.turns(view_of(WHOLE))
    assert [(s / MS, e / MS) for s, e, _ in turns] == [(100, 160), (180, 270)]
    assert sorted(turns[0][2]) == ["engine/admit", "engine/build", "engine/decode", "engine/deliver", "engine/dispatch", "engine/gauges", "engine/sync"]
    assert len(turns[1][2]["engine/build"]) == 2 and "engine/idle" not in turns[1][2]


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_trace_without_engine_spans_reads_none(name):
    # a parent commit writes none: only the dispatch events and Python calls are there
    host = [ev("PjitFunction(decode_step_paged)", 102, 103), ev("$loop.py:372 _decode_step", 101, 158)]
    assert READERS[name].read(view_of(host)) is None
    # children alone (every turn cut) are no turn either
    assert READERS[name].read(view_of(CUT_HEAD + CUT_TAIL)) is None


def test_turns_without_a_prefill():
    view = view_of(TURN_A)
    assert engine_iter_prefill_ms.read(view) is None
    assert engine_host_turn_ms.read(view) == pytest.approx(10.0)
    assert engine_gauges_ms_per_s.read(view) == pytest.approx(1.5 / 0.060)
    # idle 100-104 and 150-160, under engine/sync 103-104 and 150-153
    assert device_idle_in_host_turn.read(view) == pytest.approx(100.0 * 10 / 14)
    # a device that never idles inside a turn has no share to give
    assert device_idle_in_host_turn.read(view_of(TURN_A, [ev("fusion.1", 90, 170), ev("fusion.1", 170, 200)])) is None


@pytest.mark.parametrize("cell", ["mistral-7b-l16.chat", "mistral-7b-l16.docs"])
def test_the_tiny_traced_rehearsal_prints_every_span_metric(cell):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"] if cell in m.get("workloads", [cell]) and m["name"].partition(".")[0] in READERS}
    assert listed
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", cell, "--seed", "3000000001", "--seconds", "5", "--trace", "1", "--tiny"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert listed <= set(out["metrics"]), listed - set(out["metrics"])
    for name in listed:
        assert out["metrics"][name]["value"] >= 0.0
