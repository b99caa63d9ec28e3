"""Time a turn spends in the pass over its sinks with the engine's lock
released: ``engine/emit`` spans (``sink.emit`` for every row just read: rings,
stream transport, shared-memory writes, the sealing of retired records),
summed over the whole turns of the trace, per turn.  With ``engine_lock_wait_ms``
it splits ``engine_deliver_ms``; what is left of that is bookkeeping under the
lock.  A program that writes no such span (before PR 38) reads None."""

from benchmarks.layer_metrics import _engine_spans


def read(view):
    turns = _engine_spans.turns(view)
    if not any("engine/emit" in t[2] for t in turns):
        return None
    return _engine_spans.mean_ms(_engine_spans.inside(t, "engine/emit") for t in turns)
