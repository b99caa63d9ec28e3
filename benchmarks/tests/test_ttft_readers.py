"""The chat cell's per-layer time to first token: read over the requests a
traced run served before the profiler's capture, and nowhere else."""

import pytest

from benchmarks import stats
from benchmarks.layer_metrics import _ttft, ttft_mean_unstalled_ms, ttft_p90_unstalled_ms


def rec(due, ttft_s, *, error=None, tokens=8, budget=8, done=True):
    frames = [(due + ttft_s, 1), (due + ttft_s + 0.1, tokens - 1)] if ttft_s is not None else []
    return {"due": due, "frames": frames, "error": error, "tokens": tokens, "budget": budget, "done": (due + 1.0) if done else None}


def view(records, capture_start_s=15.0):
    counters = {"window_s": 45.0}
    if capture_start_s is not None:
        counters["capture_start_s"] = capture_start_s
    return {"counters": counters, "records": records}


SOUND = [rec(0.5 * k, 0.020 + 0.004 * k) for k in range(28)]  # due 0 .. 13.5, every one before 15 - MARGIN_S


def test_the_requests_due_before_the_capture_are_read_as_the_end_to_end_metric_read_them():
    want = [(0.020 + 0.004 * k) * 1e3 for k in range(28)]
    assert _ttft.unstalled_ms(view(SOUND)) == pytest.approx(want)
    assert ttft_p90_unstalled_ms.read(view(SOUND)) == pytest.approx(stats.percentile(want, 90))
    assert ttft_mean_unstalled_ms.read(view(SOUND)) == pytest.approx(sum(want) / len(want))


@pytest.mark.parametrize("extra", [
    rec(-2.0, 0.5),                      # the pre-roll's
    rec(14.5, 0.5),                      # inside the margin before the capture
    rec(15.0, 40.0),                     # due at the capture: waits for the profiler's stop
    rec(30.0, 55.0),                     # stalled
    rec(3.0, 0.5, error="Timeout"),      # failed
    rec(3.0, 0.5, tokens=7),             # another token count than its budget
    rec(3.0, 0.5, done=False),           # never finished
    rec(3.0, None),                      # streamed nothing
])
def test_what_met_the_stall_or_failed_is_left_out(extra):
    assert _ttft.unstalled_ms(view(SOUND + [extra])) == pytest.approx(_ttft.unstalled_ms(view(SOUND)))


@pytest.mark.parametrize("v", [view(SOUND, capture_start_s=None), view([]), view([rec(20.0, 0.1)])])
def test_nothing_to_read_is_nothing(v):
    assert ttft_p90_unstalled_ms.read(v) is None and ttft_mean_unstalled_ms.read(v) is None
