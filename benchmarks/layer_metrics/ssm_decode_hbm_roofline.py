"""Roofline share of a state-space hybrid's decode program against HBM
bandwidth: the least bytes a decode step must move (every weight and the tied
matrix once, the attending layers' K/V of the live context once, each decoding
row's state and conv window once in and once out: ``benchmarks/costs_ssm.py``)
over the published bytes/s, over the program's device time, in percent.  Rows
and the context they hold are read as ``moe_decode_hbm_roofline`` reads them
(``occupancy``)."""

from benchmarks import costs_ssm
from benchmarks.layer_metrics import decode_program_ms
from benchmarks.layer_metrics.moe_decode_hbm_roofline import occupancy


def read(view):
    ms = decode_program_ms.read(view)
    if not ms or "mamba_d_state" not in view["config"]:
        return None
    rows, live_tokens = occupancy(view)
    if rows <= 0:
        return None
    least_s = costs_ssm.decode_step_min_bytes(view["config"], rows, live_tokens) / view["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms * 1e-3)
