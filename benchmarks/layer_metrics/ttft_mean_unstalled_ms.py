"""The mean time to first token over the requests due before the profiler's
capture (``_ttft.py``): the steadier statistic beside
``ttft_p90_unstalled_ms``, which one request moves by a step."""

from benchmarks.layer_metrics import _ttft


def read(view):
    ttft = _ttft.unstalled_ms(view)
    return sum(ttft) / len(ttft) if ttft else None
