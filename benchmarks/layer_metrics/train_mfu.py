"""Model FLOP/s utilization: the FLOPs the forward and backward passes need
per step (benchmarks/costs.py; recomputation not counted) over the time from
one step's start on the device to the next (so the host's share is inside it),
over the chip's published peak.  Read from the trace, because the profiler's
own start and stop stall the traced run's host clock."""

from benchmarks import costs, trace_reduce
from benchmarks.layer_metrics import train_step_device_ms


def read(view):
    c = view["counters"]
    period = trace_reduce.period_seconds(view["trace"], train_step_device_ms.STEP.pattern)
    if not period:
        return None
    flops = costs.gpt2_train_flops_per_token(view["config"], c["seq"]) * c["tokens_per_step"] / c["chips"]
    return 100.0 * flops / period / view["peaks"]["bf16_flops_per_s"]
