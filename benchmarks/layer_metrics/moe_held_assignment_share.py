"""Of the window's routed assignments (every row's ``num_experts_per_tok``
choices over all published experts), the share that fell on experts this
replica holds: ``moe_assignments_held / moe_assignments_seen`` from the
engine's counters (``engine_stats()``), between the driver's two snapshots at
the window's ends.  With a quarter of the experts held and even routing it is
0.25; above it this replica does more than its deployment share of the expert
work, below it less."""


def read(view):
    seen = view["counters"].get("moe_assignments_seen")
    held = view["counters"].get("moe_assignments_held")
    if not seen or held is None:
        return None
    return held / seen
