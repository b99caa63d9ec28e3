"""Roofline share of the selective-scan kernel: the least time its calls in
the trace could take (``costs_ssm.scan_min_seconds``: the larger of operations
over the peak and bytes over the bandwidth -- the bytes bind -- for a chunk's
mean valid rows and for a decode step's mean rows) over the time the trace
shows for them, in percent."""

from benchmarks.layer_metrics import _ssm_kernel


def read(view):
    if "mamba_d_state" not in view["config"]:
        return None
    least = took = 0.0
    for kind, (secs, n) in _ssm_kernel.calls(view["trace"]).items():
        one = _ssm_kernel.least_seconds(view, kind)
        if one is None:
            return None
        least, took = least + n * one, took + secs
    return 100.0 * least / took if took else None
