"""How unevenly the window's tokens were routed: the busiest expert's
assignments over the mean expert's, from the difference of the engine's
per-expert counter (``engine_stats()["moe_expert_load"]``, summed over layers)
between the driver's two snapshots at the window's ends.  1.0 is even; a
dropless layer pays for imbalance in time (the busiest expert's rows), a
layer with a capacity would pay in dropped tokens."""


def read(view):
    load = view["counters"].get("moe_expert_load")
    if not load or sum(load) <= 0:
        return None
    return max(load) / (sum(load) / len(load))
