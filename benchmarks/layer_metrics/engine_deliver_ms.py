"""Time a turn spends handing its tokens on: ``engine/deliver`` spans
(``note_token``, the sinks, the stream transport and its shared-memory
writes, retirement) summed over the whole turns of the trace, per turn."""

from benchmarks.layer_metrics import _engine_spans


def read(view):
    return _engine_spans.mean_ms(_engine_spans.inside(t, "engine/deliver") for t in _engine_spans.turns(view))
