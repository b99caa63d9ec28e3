"""Which executed program is the engine's decode step and which its prefill
chunk.  Both are jitted ``functools.partial`` objects, so the device trace
names both ``jit__unknown(<fingerprint>)`` (named scopes on them are on the
list for the ``tracing`` issue).  The host plane does name them: every
dispatch of a jitted function is a host event ``PjitFunction(<function>)``,
here ``PjitFunction(decode_step_paged)`` and
``PjitFunction(prefill_chunk_paged)`` (models/llama.py).  The device runs
programs in the order they were dispatched, so executions and dispatches
pair up in order, once the executions whose dispatch was before the trace
began are left out."""

from benchmarks import trace_reduce

DISPATCH = r"^PjitFunction\((decode_step_paged|prefill_chunk_paged)\)$"
SKEW_NS = 2e6
KIND = {"PjitFunction(decode_step_paged)": "decode", "PjitFunction(prefill_chunk_paged)": "prefill"}


def classify(view):
    """{"decode": [seconds, ...], "prefill": [...]}: device durations of each
    program execution in the trace, cached on the view."""
    if "_engine_programs" in view:
        return view["_engine_programs"]
    dispatches, end = [], 0.0
    for ev in trace_reduce.host_events(view["planes"], DISPATCH):
        if ev[1] >= end:  # the event is written twice, one nested in the other: keep the outer
            dispatches.append(ev)
            end = ev[1] + ev[2]
    modules = view["trace"]["first_device_modules"]
    # The first executions of the trace may have been dispatched before it
    # began (a program is dispatched while the one before it still runs), and
    # the first dispatches may be of programs that began before it.  So try
    # leaving out the first k executions and the first j dispatches: in the
    # right pairing a program starts right after its dispatch (at most a
    # prefill chunk later), in a pairing shifted by one an iteration later.
    # The device's clock may read a little ahead of the host's: a start up to
    # SKEW_NS before the dispatch is allowed.
    best = None
    for k in range(min(4, len(modules))):
        for j in range(min(4, len(dispatches))):
            pairs = list(zip(dispatches[j:], modules[k:]))
            lags = [m[1] - d[1] for d, m in pairs]
            if len(pairs) < 2 or min(lags) < -SKEW_NS:
                continue
            score = (sum(abs(x) for x in lags) / len(lags), k + j)
            if best is None or score < best[0]:
                best = (score, pairs)
    out = {"decode": [], "prefill": []}
    for d, m in best[1] if best else []:
        out[KIND[d[0]]].append(m[2] * 1e-9)
    view["_engine_programs"] = out
    return out


def mean_ms(view, kind):
    xs = classify(view)[kind]
    return 1e3 * sum(xs) / len(xs) if xs else None
