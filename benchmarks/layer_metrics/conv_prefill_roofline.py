"""Roofline share of a short-convolution expert model's prefill program (one
chunk of one prompt): the least time of a chunk -- the larger of its ROUTED
FLOPs over the peak (4 experts a token, not 64; attention in the attending
layers only) and its bytes over the bandwidth,
``costs_conv.prefill_chunk_min_seconds`` -- over the program's device time, in
percent.  What the program's masked contraction over all experts costs beyond
the routed FLOPs is the program's, not the yardstick's, and shows here as the
gap.  A chunk's valid rows and the context its last row sees are the means
over the chunks the measured prompts need (``ceil(prompt / chunk)`` each, the
last one partly padded)."""

import math

from benchmarks import costs_conv
from benchmarks.layer_metrics import prefill_program_ms


def read(view):
    ms = prefill_program_ms.read(view)
    if not ms or "conv_L_cache" not in view["config"]:
        return None
    chunk = int(view["config"]["engine"]["prefill_chunk"])
    rows, ends = [], []
    for r in view["records"]:
        for k in range(math.ceil(r["prompt_len"] / chunk)):
            end = min(r["prompt_len"], (k + 1) * chunk)
            rows.append(end - k * chunk)
            ends.append(end)
    if not rows:
        return None
    least_s = costs_conv.prefill_chunk_min_seconds(view["config"], sum(rows) / len(rows), sum(ends) / len(ends), view["peaks"])
    return 100.0 * least_s / (ms * 1e-3)
