"""The 90th percentile of the time to first token over the requests due before
the profiler's capture (``_ttft.py``): what ``ttft_p90_ms`` measured, over the
third of the window a traced run serves unhindered."""

from benchmarks import stats
from benchmarks.layer_metrics import _ttft


def read(view):
    ttft = _ttft.unstalled_ms(view)
    return stats.percentile(ttft, 90) if ttft else None
