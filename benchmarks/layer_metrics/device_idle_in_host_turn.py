"""Of the first device's idle time inside whole engine turns, the share
during which the engine thread was doing its own work: inside an
``engine/iteration`` and outside its ``engine/sync`` spans (``engine/idle``
never lies inside a turn).  Idle under ``engine/sync`` is the latency of the
read-back; idle outside any turn is lack of requests; the rest is what
shortening the host's turn can give back to the device.  The device's clock
may read up to ~2 ms off the host's (``_engine_programs.SKEW_NS``), which
moves idle time across a span's edge: read this share next to the idle
share, not to the last digit."""

from benchmarks import trace_reduce
from benchmarks.layer_metrics import _engine_spans


def read(view):
    turns = _engine_spans.turns(view)
    ops = view["trace"]["first_device_ops"]
    if not turns or not ops:
        return None
    busy = trace_reduce.union_intervals((s, s + d) for _, s, d in ops)
    idle = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]  # gaps between operations: inside the device's own window
    idle_in_turns = _engine_spans.intersect(idle, [(t[0], t[1]) for t in turns])
    if not idle_in_turns:
        return None
    sync = [iv for t in turns for iv in t[2].get("engine/sync", ())]  # by start: turns and their children are
    waiting = _engine_spans.total(_engine_spans.intersect(idle_in_turns, sync))
    whole = _engine_spans.total(idle_in_turns)
    return 100.0 * (whole - waiting) / whole
