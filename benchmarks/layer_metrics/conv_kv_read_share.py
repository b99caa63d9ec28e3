"""Of the least bytes of a decode step (``costs_conv.decode_step_min_bytes``),
the share that is K/V: the positions the step's rows hold
(``ctx_positions_live / decode_steps``) times what the pool keeps a position
(``cache_bytes_per_position``), both the engine's own counts
(``engine_stats()``).  With two attending layers in ten it is a twentieth of a
step here; were all ten layers attention the same positions would be five
times that."""

from benchmarks import costs_conv
from benchmarks.layer_metrics.mla_decode_hbm_roofline import occupancy


def read(view):
    per_position = view["counters"].get("cache_bytes_per_position")
    if not per_position or "conv_L_cache" not in view["config"]:
        return None
    rows, live = occupancy(view)
    if rows <= 0:
        return None
    cache = live * per_position
    return 100.0 * cache / (costs_conv.weight_bytes(view["config"], rows) + 2.0 * rows * costs_conv.window_bytes_per_slot(view["config"]) + cache)
