"""Device duration of one call of the engine's jitted prefill program
(``prefill_chunk_paged``, one chunk of one prompt): mean over its executions
in the trace."""

from benchmarks.layer_metrics import _engine_programs


def read(view):
    return _engine_programs.mean_ms(view, "prefill")
