"""Mean number of engine slots in use, sampled twice a second through
``engine_stats`` during the window."""


def read(view):
    s = view["counters"].get("slot_samples")
    return sum(s) / len(s) if s else None
