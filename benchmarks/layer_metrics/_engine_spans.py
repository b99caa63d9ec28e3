"""The engine thread's turn, from the spans the program writes on it
(``ray_tpu/serve/tracing.py span``, vocabulary ``ENGINE_SPANS``): one
``engine/iteration`` per turn of the loop, and inside it ``engine/admit``,
``engine/prefill`` and ``engine/decode`` (each ``engine/build`` ->
``engine/dispatch`` -> ``engine/sync`` -> ``engine/deliver``),
``engine/flush`` and ``engine/gauges``; ``engine/idle`` lies between turns.
They are ``TraceAnnotation``s, so they are on the device trace's clock.

Spans are taken from the host planes by name and nested by containment.
The profiler keeps a span only if it began and ended inside the capture, so
a turn that the capture's ends cut has no ``engine/iteration``; what is left
of its children lies in no whole turn and is left out here.  A program that
writes no such span (a parent commit) gives no turn, and every reader of
this module then returns None."""

import bisect

from benchmarks import trace_reduce

TURN = "engine/iteration"


def spans(view):
    """Every ``engine/*`` event of the host planes, by start; one pass over
    the trace (the Python tracer writes very many events), cached on the view."""
    if "_engine_spans" not in view:
        view["_engine_spans"] = trace_reduce.host_events(view["planes"], r"^engine/")
    return view["_engine_spans"]


def turns(view):
    """Whole turns in the trace, by start: ``[(start_ns, end_ns, {span name:
    [(start_ns, end_ns), ...]}), ...]``.  Cached on the view."""
    if "_engine_turns" in view:
        return view["_engine_turns"]
    out = [(s, s + d, {}) for name, s, d in spans(view) if name == TURN]
    starts = [t[0] for t in out]
    for name, s, d in spans(view):
        i = bisect.bisect_right(starts, s) - 1
        if name != TURN and i >= 0 and s + d <= out[i][1]:
            out[i][2].setdefault(name, []).append((s, s + d))
    view["_engine_turns"] = out
    return out


def inside(turn, name) -> float:
    """Nanoseconds of one turn spent in its spans called ``name``."""
    return sum(e - s for s, e in turn[2].get(name, ()))


def mean_ms(values):
    values = list(values)
    return 1e-6 * sum(values) / len(values) if values else None


intersect = trace_reduce.intersect_intervals  # intervals common to two sorted lists of disjoint (start, end)


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)
