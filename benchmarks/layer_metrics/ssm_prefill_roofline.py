"""Roofline share of a state-space hybrid's prefill program (one chunk of one
prompt): the least time of a chunk -- the larger of its FLOPs over the peak
and its bytes over the bandwidth, ``costs_ssm.prefill_chunk_min_seconds`` --
over the program's device time, in percent.  A chunk's valid rows and the
context its last row sees are the means over the chunks the measured prompts
need (``_ssm_kernel.chunks``)."""

from benchmarks import costs_ssm
from benchmarks.layer_metrics import _ssm_kernel, prefill_program_ms


def read(view):
    ms = prefill_program_ms.read(view)
    if not ms or "mamba_d_state" not in view["config"]:
        return None
    cut = _ssm_kernel.chunks(view)
    if not cut:
        return None
    rows, ends = sum(n for n, _ in cut) / len(cut), sum(e for _, e in cut) / len(cut)
    return 100.0 * costs_ssm.prefill_chunk_min_seconds(view["config"], rows, ends, view["peaks"]) / (ms * 1e-3)
