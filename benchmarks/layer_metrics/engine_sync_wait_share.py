"""The host's slack under one decode step in flight: of the time of the whole
turns of the trace, the share the engine thread spent inside ``engine/sync``,
waiting in a blocking read for a device that was still busy.  While it is large
the host's work is hidden under the device; as it nears zero the host sets the
pace.  It is the complement of ``engine_host_turn_ms`` over the turn, and takes
``device_idle_in_host_turn``'s place as the judge of the host's turn: that one
divides an idle time that overlap has all but removed."""

from benchmarks.layer_metrics import _engine_spans


def read(view):
    turns = _engine_spans.turns(view)
    if not turns:
        return None
    return 100.0 * sum(_engine_spans.inside(t, "engine/sync") for t in turns) / sum(t[1] - t[0] for t in turns)
