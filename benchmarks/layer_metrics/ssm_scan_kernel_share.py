"""Share of the device's busy time spent in the selective-scan kernel's calls
(both programs'), in percent."""

from benchmarks.layer_metrics import _ssm_kernel


def read(view):
    tr = view["trace"]
    secs = _ssm_kernel.seconds(tr)
    if not secs or not tr["busy_s"]:
        return None
    return 100.0 * secs / tr["busy_s"]
