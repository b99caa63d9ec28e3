"""Time a turn's work waits for the engine's lock: ``engine/lock`` spans (the
ACQUISITION alone, at admission, at ``next_prefill`` and once a delivery),
summed over the whole turns of the trace, per turn.  What it waits for is
``submit()`` or an ``engine_stats`` caller on the replica's other threads.
A program that writes no such span (before PR 38) reads None."""

from benchmarks.layer_metrics import _engine_spans


def read(view):
    turns = _engine_spans.turns(view)
    if not any("engine/lock" in t[2] for t in turns):
        return None
    return _engine_spans.mean_ms(_engine_spans.inside(t, "engine/lock") for t in turns)
