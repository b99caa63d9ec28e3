"""Per step, the time collective operations ran on a device while nothing
else did (cells on several chips only)."""

import re

from benchmarks.layer_metrics import train_step_device_ms

COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute", re.I)


def read(view):
    tr = view["trace"]
    if tr["devices"] < 2:
        return None
    from benchmarks import trace_reduce

    steps = sum(n for name, n in tr["module_count"].items() if train_step_device_ms.STEP.search(name))
    if not steps:
        return None
    _, exposed = trace_reduce.exposed_seconds(tr, lambda name: bool(COLLECTIVE.search(name)))
    return 1e3 * exposed / steps
