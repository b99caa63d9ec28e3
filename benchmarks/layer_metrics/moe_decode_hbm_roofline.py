"""Roofline share of an expert model's decode program against HBM bandwidth:
the least bytes a decode step must read (attention matrices, router, the
experts some row is routed to, the head, and the live context's K/V once:
``benchmarks/costs_moe.py``) over the published bytes/s, over the program's
device time, in percent.

Rows are the engine's own count of slots in the decode phase, sampled twice a
second through ``engine_stats`` while the profiler captured, i.e. over the
calls whose time is the denominator (``counters["slots_decode_samples"]``,
kept by ``drivers/serve_moe.py``; after the capture the profiler's write stalls
the replica's intake for seconds and the slots drain).  The context a decoding row holds is what the
client saw: a request decodes for one turn a token, so the mean over decoding
rows weights each completed request by its answer's tokens, and a request
holds its prompt plus half its answer while it decodes.  Neither needs a
first-frame time, so buffered and streamed cells read alike."""

from benchmarks import costs_moe
from benchmarks.layer_metrics import decode_program_ms


def occupancy(view):
    """(mean sequences decoding, mean context tokens they hold together)."""
    samples = view["counters"].get("slots_decode_samples")
    done = [r for r in view["records"] if r.get("done") is not None and r.get("tokens")]
    if not samples or not done:
        return 0.0, 0.0
    rows = sum(samples) / len(samples)
    held = sum(r["tokens"] * (r["prompt_len"] + r["tokens"] / 2.0) for r in done) / sum(r["tokens"] for r in done)
    return rows, rows * held


def read(view):
    ms = decode_program_ms.read(view)
    if not ms or "num_experts" not in view["config"]:
        return None
    rows, live_tokens = occupancy(view)
    if rows <= 0:
        return None
    least_s = costs_moe.decode_step_min_bytes(view["config"], rows, live_tokens) / view["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms * 1e-3)
