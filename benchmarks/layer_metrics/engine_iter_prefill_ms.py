"""Mean duration of the engine turns that ran a prefill chunk beside their
decode step (the ``engine/iteration`` spans that contain an
``engine/prefill``).  A token that waits through such a turn is the slow
population of the token gap: its p95 is drawn from these turns, its median
from the others, and the mean over all turns is neither."""

from benchmarks.layer_metrics import _engine_spans


def read(view):
    return _engine_spans.mean_ms(t[1] - t[0] for t in _engine_spans.turns(view) if "engine/prefill" in t[2])
