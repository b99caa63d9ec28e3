"""Roofline share of the decode program against HBM bandwidth: the least
bytes a decode step must read (the weights once and the K/V of the live
context once, benchmarks/costs.py) over the published bytes/s, over the
program's device time, in percent.  Decoding a few sequences is bound by
bytes, not FLOPs.  The live context is what the client saw: for each measured
request, its prompt plus the tokens it had received, averaged over its
lifetime, times the mean number of slots in use."""

from benchmarks import costs
from benchmarks.layer_metrics import decode_program_ms


def read(view):
    ms = decode_program_ms.read(view)
    recs = [r for r in view["records"] if r.get("done") is not None and r.get("sent") is not None]
    if not ms or not recs:
        return None
    c = view["counters"]
    # token-seconds of context held, over the window: prompt + half of the answer for a request's lifetime
    held = sum((r["prompt_len"] + r["tokens"] / 2.0) * (r["done"] - r["sent"]) for r in recs)
    live_tokens = held / c["window_s"]
    least_s = costs.decode_step_min_bytes(view["config"], live_tokens) / view["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms * 1e-3)
