"""Roofline share of a short-convolution expert model's decode program
against HBM bandwidth: the least bytes a decode step must read (every held
matrix once with the experts some row is routed to, the tied head once, the
live positions' K/V of the ATTENDING layers once, the decoding rows' conv
windows in and out: ``benchmarks/costs_conv.py``) over the published bytes/s,
over the program's device time, in percent.  It counts the work and not the
implementation: a program that runs every expert on every row, or keeps K/V
for a conv layer, reads lower here.

Rows and the positions they hold are the engine's own counts, taken as
``mla_decode_hbm_roofline`` takes them (``occupancy``: ``slots_decode`` while
the profiler captured; ``ctx_positions_live`` over ``decode_steps`` between the
replies nearest the capture's ends), so over the steps whose time is the
denominator."""

from benchmarks import costs_conv
from benchmarks.layer_metrics import decode_program_ms
from benchmarks.layer_metrics.mla_decode_hbm_roofline import occupancy


def read(view):
    ms = decode_program_ms.read(view)
    if not ms or "conv_L_cache" not in view["config"]:
        return None
    rows, live = occupancy(view)
    if rows <= 0:
        return None
    least_s = costs_conv.decode_step_min_bytes(view["config"], rows, live) / view["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms * 1e-3)
