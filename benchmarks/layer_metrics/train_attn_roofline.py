"""Roofline share of the attention kernels in the train step: the least time
the chip could take for the kernels' work (the larger of FLOPs over peak
FLOP/s and bytes over peak bytes/s, from shapes, benchmarks/costs.py) over the
time the trace shows for them, per step, in percent."""

from benchmarks import costs
from benchmarks.layer_metrics import _kernels, train_step_device_ms


def read(view):
    tr, cfg, c = view["trace"], view["config"], view["counters"]
    secs = _kernels.attention_seconds(tr)
    steps = sum(n for name, n in tr["module_count"].items() if train_step_device_ms.STEP.search(name))
    if not secs or not steps:
        return None
    head_dim = cfg["n_embd"] // cfg["n_head"]
    args = (c["batch_per_chip"], cfg["n_head"], c["seq"], head_dim, cfg["n_layer"])
    least = max(
        costs.causal_attention_train_flops(*args) / view["peaks"]["bf16_flops_per_s"],
        costs.causal_attention_train_bytes(*args) / view["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least / (secs / steps)
