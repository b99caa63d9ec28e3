"""How the selective-scan kernel is named in a device trace, and what its
calls should cost.  ``ops/selective_scan.py`` names its Pallas call
``ssm_scan``; on the ``XLA Ops`` line its calls appear as ``ssm_scan.N``, and
with two programs in a trace as ``<program>/ssm_scan.N``
(``trace_reduce._by_module``): one call a Mamba layer in a prefill chunk (one
sequence, the chunk's rows) and in a decode step (one row a slot).  A program
without the kernel (a parent commit, the CPU rehearsal's plain form) gives no
such operation and every reader of this module returns None."""

import math
import re

from benchmarks import costs_ssm

KERNEL = re.compile(r"ssm_scan")
PROGRAM = {"prefill": re.compile(r"prefill_chunk_paged"), "decode": re.compile(r"decode_step_paged")}


def calls(trace):
    """{"prefill" | "decode": (seconds, calls)} of the kernel's operations in whole program executions."""
    out = {}
    for name, secs in trace["op_s"].items():
        if not KERNEL.search(name):
            continue
        for kind, rx in PROGRAM.items():
            if rx.search(name):
                s, n = out.get(kind, (0.0, 0.0))
                out[kind] = (s + secs, n + trace["op_count"][name])
    return out


def seconds(trace) -> float:
    return sum(s for name, s in trace["op_s"].items() if KERNEL.search(name))


def chunks(view):
    """(valid rows, context its last row sees) of every chunk the measured
    prompts need: ``ceil(prompt / chunk)`` each, the last one partly padded."""
    chunk = int(view["config"]["engine"]["prefill_chunk"])
    out = []
    for r in view["records"]:
        for k in range(math.ceil(r["prompt_len"] / chunk)):
            end = min(r["prompt_len"], (k + 1) * chunk)
            out.append((end - k * chunk, end))
    return out


def least_seconds(view, kind: str):
    """Least time of one call of ``kind``: a chunk's mean valid rows of one
    sequence (the chunks the measured prompts need), or a decode step's mean
    rows (``counters["slots_decode_samples"]``)."""
    cfg = view["config"]
    if kind == "decode":
        samples = view["counters"].get("slots_decode_samples")
        rows = sum(samples) / len(samples) if samples else 0.0
        return costs_ssm.scan_min_seconds(cfg, rows, rows, view["peaks"]) if rows > 0 else None
    rows = [n for n, _ in chunks(view)]
    return costs_ssm.scan_min_seconds(cfg, sum(rows) / len(rows), 1.0, view["peaks"]) if rows else None
