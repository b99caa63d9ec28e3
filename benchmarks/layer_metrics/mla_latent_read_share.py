"""Of the least bytes of a decode step (``costs_mla.decode_step_min_bytes``),
the share that is the cache: the positions the step's rows hold
(``ctx_positions_live / decode_steps``) times what the pool keeps a position
(``cache_bytes_per_position``), both the engine's own counts
(``engine_stats()``).  With one latent row a position it is an eighth of a
step here; held as K and V heads the same positions would be most of it."""

from benchmarks import costs_mla
from benchmarks.layer_metrics.mla_decode_hbm_roofline import occupancy


def read(view):
    per_position = view["counters"].get("cache_bytes_per_position")
    if not per_position or "kv_lora_rank" not in view["config"]:
        return None
    rows, live = occupancy(view)
    if rows <= 0:
        return None
    cache = live * per_position
    return 100.0 * cache / (costs_mla.weight_bytes(view["config"], rows) + cache)
