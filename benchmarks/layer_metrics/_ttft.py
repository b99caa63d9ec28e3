"""Time to first token as a traced run can still read it.

``ttft_p90_ms`` was an end-to-end metric of the chat cell until PR 43: over the
71 requests of a 45 s window the p90 is ONE request's time, and on the fixed
schedule it stands at the edge of a step in the distribution (the prompts of
~1000 tokens, three or four chunk turns, read 104-120 ms; the next below
86-95 ms), so one request served a decode turn later moves it by 12 ms: two
runs of one seed read 91.6 and 103.4 (PERF.md section 2).  It is reported per
layer since, with the mean beside it, which the same four runs read within
2.6%.

Per-layer metrics come from the TRACED run, in which ``bench_trace_stop``
holds the replica's intake for most of a minute after the capture
(``drivers/serve.py``): every request due from the capture on waits for it.
So these readers take the requests due in the window at least ``MARGIN_S``
before the capture starts (a third into the window: ~22 of 71), whose first
frame has long arrived by then; measured as the end-to-end metric was: from
the instant a request was DUE to its first streamed frame at the client."""

MARGIN_S = 1.0  # a first frame takes 20-140 ms; a second keeps the profiler's start off the last of them


def unstalled_ms(view):
    """TTFTs (ms) of the sound requests due in [0, capture start - MARGIN_S);
    empty where the run was not traced or streamed nothing."""
    start = view["counters"].get("capture_start_s")
    if start is None:
        return []
    return [
        (r["frames"][0][0] - r["due"]) * 1e3
        for r in view.get("records", [])
        if r.get("due") is not None and 0.0 <= r["due"] < start - MARGIN_S
        and r["frames"] and not r["error"] and r["done"] is not None and r["tokens"] == r["budget"]
    ]
