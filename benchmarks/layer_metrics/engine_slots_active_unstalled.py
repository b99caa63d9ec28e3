"""Mean number of engine slots in use while the replica ran unhindered: the
``slots_active`` of every ``engine_stats`` reply asked from the window's start
to the END OF THE PROFILER'S CAPTURE (a traced run asks twice a second).

``engine_slots_active_mean`` averages the same samples over the whole window.
In a traced run that includes the seconds after the capture in which
``bench_trace_stop`` (``drivers/serve.py``) writes the profile inside the
replica and holds its intake: the engine thread goes on, finishes what is
queued and then drains its slots.  A cell with a long queue of long requests
(docs) does not show it; a saturated cell of short ones does (32 slots read 25
over the window and 31-32 up to the capture's end: PERF.md section 6, PR 28).
The samples are kept by ``drivers/serve_moe.py``
(``counters["slots_active_unstalled"]``); where a driver keeps none, nothing
is read."""


def read(view):
    s = view["counters"].get("slots_active_unstalled")
    return sum(s) / len(s) if s else None
