"""Engine-thread time that publishing the occupancy gauges takes, per second
of serving: ``engine/gauges`` spans (written only when the gauges publish)
between the start of the first and the end of the last whole turn of the
trace, over that stretch.  Gauges published from an idle turn in between
count too: the thread is the same."""

from benchmarks.layer_metrics import _engine_spans


def read(view):
    turns = _engine_spans.turns(view)
    if not turns:
        return None
    lo, hi = turns[0][0], turns[-1][1]
    spent = sum(max(0.0, min(s + d, hi) - max(s, lo)) for name, s, d in _engine_spans.spans(view) if name == "engine/gauges")
    return 1e3 * spent / (hi - lo)
