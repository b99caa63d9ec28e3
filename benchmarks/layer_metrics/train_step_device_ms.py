"""Device duration of one execution of the jitted train step: the mean of the
``XLA Modules`` events of the step program in the trace."""

import re

STEP = re.compile(r"jit_step")


def read(view):
    tr = view["trace"]
    names = [n for n in tr["module_s"] if STEP.search(n)]
    count = sum(tr["module_count"][n] for n in names)
    if not count:
        return None
    return 1e3 * sum(tr["module_s"][n] for n in names) / count
