"""The engine thread's own work a turn: mean over the whole
``engine/iteration`` spans of the trace of (duration - time inside its
``engine/sync`` spans, the blocking read of the sampled tokens).  What is
left is building arguments, dispatching, delivering frames, admission and
gauges: host time that the device program does not hide."""

from benchmarks.layer_metrics import _engine_spans


def read(view):
    return _engine_spans.mean_ms((t[1] - t[0]) - _engine_spans.inside(t, "engine/sync") for t in _engine_spans.turns(view))
