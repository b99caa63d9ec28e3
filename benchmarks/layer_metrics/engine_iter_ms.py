"""Wall time of one engine iteration: seconds between the two
``engine_stats`` snapshots at the window's ends over the iterations between
them (``InferenceEngine.stats()['iterations']``)."""


def read(view):
    c = view["counters"]
    if not c.get("iterations"):
        return None
    return 1e3 * c["stats_interval_s"] / c["iterations"]
