"""What one gauge tick costs the engine thread: mean duration of the
``engine/gauges`` spans of the trace (the profiler keeps a span only if it
began and ended inside the capture, so each is whole), from an idle turn or
inside one.  Per tick, not per second: whether a capture holds five ticks or
six does not move it.  None where the capture holds no tick."""

from benchmarks.layer_metrics import _engine_spans


def read(view):
    return _engine_spans.mean_ms(d for name, _, d in _engine_spans.spans(view) if name == "engine/gauges")
