"""Device duration of one call of the engine's jitted decode program
(``decode_step_paged``): mean over its executions in the trace."""

from benchmarks.layer_metrics import _engine_programs


def read(view):
    return _engine_programs.mean_ms(view, "decode")
