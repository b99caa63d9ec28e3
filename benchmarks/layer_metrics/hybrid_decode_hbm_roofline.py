"""Roofline share of a hybrid model's decode program against HBM bandwidth:
the least bytes a decode step must move (mixers, routers and shared experts,
the held experts some row is routed to, the head's slice, the full layers' K/V
of the live context once, and each decoding row's recurrent state once in and
once out: ``benchmarks/costs_hybrid.py``) over the published bytes/s, over the
program's device time, in percent.  Rows and the context they hold are read as
``moe_decode_hbm_roofline`` reads them (``occupancy``)."""

from benchmarks import costs_hybrid
from benchmarks.layer_metrics import decode_program_ms
from benchmarks.layer_metrics.moe_decode_hbm_roofline import occupancy


def read(view):
    ms = decode_program_ms.read(view)
    if not ms or "linear_num_value_heads" not in view["config"]:
        return None
    rows, live_tokens = occupancy(view)
    if rows <= 0:
        return None
    least_s = costs_hybrid.decode_step_min_bytes(view["config"], rows, live_tokens) / view["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms * 1e-3)
