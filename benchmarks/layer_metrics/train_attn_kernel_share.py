"""Share of the device's busy time spent in the attention kernels (the splash
kernel's forward and backward Pallas calls), in percent."""

from benchmarks.layer_metrics import _kernels


def read(view):
    tr = view["trace"]
    secs = _kernels.attention_seconds(tr)
    if not secs or not tr["busy_s"]:
        return None
    return 100.0 * secs / tr["busy_s"]
