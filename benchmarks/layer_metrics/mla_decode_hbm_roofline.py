"""Roofline share of a latent-attention model's decode program against HBM
bandwidth: the least bytes a decode step must read (attention, router, shared
and dense matrices, the experts some row is routed to, the head, and the live
positions' latent rows ONCE for all heads: ``benchmarks/costs_mla.py``) over
the published bytes/s, over the program's device time, in percent.  It counts
the work and not the implementation: a program that expands the cache into
K and V heads, or reads it once a head, reads lower here.

Rows are the engine's own count of slots in the decode phase while the
profiler captured (``counters["slots_decode_samples"]``, kept by
``drivers/serve_moe.py``).  The positions they hold are the engine's own
count too: ``ctx_positions_live`` over ``decode_steps`` (``engine_stats()``),
between the replies nearest the capture's ends (``drivers/serve_mla_moe.py``),
so over the steps whose time is the denominator."""

from benchmarks import costs_mla
from benchmarks.layer_metrics import decode_program_ms


def occupancy(view):
    """(mean sequences decoding, mean positions they hold together) of a decode step."""
    c = view["counters"]
    samples, steps = c.get("slots_decode_samples"), c.get("decode_steps")
    if not samples or not steps or "ctx_positions_live" not in c:
        return 0.0, 0.0
    return sum(samples) / len(samples), c["ctx_positions_live"] / steps


def read(view):
    ms = decode_program_ms.read(view)
    if not ms or "kv_lora_rank" not in view["config"]:
        return None
    rows, live = occupancy(view)
    if rows <= 0:
        return None
    least_s = costs_mla.decode_step_min_bytes(view["config"], rows, live) / view["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms * 1e-3)
