"""The engine thread's turn as profiler spans (serve/tracing.py ``span``,
vocabulary ``ENGINE_SPANS``) and the stable names of the engine's jitted
programs: a bare ``InferenceEngine`` on a tiny llama serves mixed-length
requests under ``jax.profiler.start_trace`` and the file is read back.
CPU throughout, no timing assertions."""

import glob
import os
import time
import types

import numpy as np
import pytest

pytestmark = pytest.mark.serve_engine

PROMPTS = [[5, 7, 9], [3], list(range(1, 12)), [4, 4], list(range(20, 29))]
BUDGET = 6
LEAVES = ("engine/build", "engine/dispatch", "engine/sync", "engine/deliver")
DISPATCHES = {"PjitFunction(decode_step_paged)", "PjitFunction(prefill_chunk_paged)"}


def _serve(eng):
    reqs = [eng.submit(p, BUDGET) for p in PROMPTS]
    return [r.sink.result(timeout=180) for r in reqs]


def _inside(child, parent):
    return parent[1] <= child[1] and child[1] + child[2] <= parent[1] + parent[2]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced stretch of a warm engine: its engine/* and PjitFunction
    events by thread line, the tokens it produced, and the counters."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from ray_tpu.serve.llm import ShardedLLM

    llm = ShardedLLM(LlamaConfig.tiny(compute_dtype=jnp.float32), tp=1, init="random")
    eng = InferenceEngine(
        llm,
        EngineConfig(
            num_slots=4, page_size=4, max_seq_len=48, prefill_chunk=4,
            max_new_tokens=BUDGET, gauge_period_s=0.01,
        ),
        deployment="spans",
    )
    try:
        warm = _serve(eng)  # compiles both programs outside the trace
        logdir = str(tmp_path_factory.mktemp("engine_trace"))
        before = eng.stats()["iterations"]
        jax.profiler.start_trace(logdir)
        try:
            outs = _serve(eng)
            time.sleep(0.15)  # a few idle turns: engine/idle, and gauges outside an iteration
        finally:
            # the engine is idle here, so no iteration is cut by the trace's end
            jax.profiler.stop_trace()
        iterations = eng.stats()["iterations"] - before
        (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
        lines = {}
        for plane in ProfileData.from_file(path).planes:
            for i, line in enumerate(plane.lines):
                evs = [
                    (e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(("engine/", "PjitFunction("))
                ]
                if evs:
                    lines[(plane.name, line.name, i)] = sorted(evs, key=lambda ev: (ev[1], -ev[2]))
        yield {
            "eng": eng, "llm": llm, "lines": lines, "warm": warm, "outs": outs,
            "iterations": iterations,
            "spans": [ev for evs in lines.values() for ev in evs if ev[0].startswith("engine/")],
        }
    finally:
        eng.shutdown()


def _named(traced, name):
    return [ev for ev in traced["spans"] if ev[0] == name]


def test_span_names_are_the_vocabulary(traced):
    from ray_tpu.serve.tracing import ENGINE_SPANS

    seen = {ev[0] for ev in traced["spans"]}
    assert seen <= set(ENGINE_SPANS), seen - set(ENGINE_SPANS)
    # every span but engine/flush must appear: no stream sink lags here
    assert seen >= set(ENGINE_SPANS) - {"engine/flush"}, set(ENGINE_SPANS) - seen


def test_spans_lie_on_one_thread_and_nest(traced):
    holders = [k for k, evs in traced["lines"].items() if any(ev[0].startswith("engine/") for ev in evs)]
    assert len(holders) == 1, holders
    spans = sorted(traced["spans"], key=lambda ev: (ev[1], -ev[2]))
    stack = []
    for ev in spans:  # properly nested: a span either contains the next or ends before it
        while stack and ev[1] >= stack[-1][1] + stack[-1][2]:
            stack.pop()
        assert not stack or _inside(ev, stack[-1]), (ev, stack[-1])
        stack.append(ev)


@pytest.mark.parametrize("child", ["engine/admit", "engine/prefill", "engine/decode", *LEAVES, "engine/gauges", "engine/idle"])
def test_span_parents(traced, child):
    iterations = _named(traced, "engine/iteration")
    steps = _named(traced, "engine/prefill") + _named(traced, "engine/decode")
    events = _named(traced, child)
    assert events
    for ev in events:
        in_iteration = any(_inside(ev, it) for it in iterations)
        if child in LEAVES:
            assert any(_inside(ev, st) for st in steps), ev
        elif child == "engine/idle":
            assert not in_iteration, ev
        elif child != "engine/gauges":  # gauges also publish from an idle turn
            assert in_iteration, ev
    if child == "engine/admit":
        assert len(events) == len(iterations)


def test_iteration_spans_count_the_engines_iterations(traced):
    assert traced["iterations"] > 0
    assert len(_named(traced, "engine/iteration")) == traced["iterations"]
    # a prefill span per prompt chunk (its first token is read under engine/decode)
    assert len(_named(traced, "engine/prefill")) == sum(-(-len(p) // 4) for p in PROMPTS)


def test_a_turn_dispatches_its_step_before_it_reads(traced):
    """One decode step in flight, as spans: every blocking read lies in an
    ``engine/decode`` (a chunk's span holds its dispatch alone: its token is
    read once the step it joins is out), a decode span's dispatch comes
    before its reads, and turns that do both are the rule."""
    prefills, decodes = _named(traced, "engine/prefill"), _named(traced, "engine/decode")
    syncs, dispatches = _named(traced, "engine/sync"), _named(traced, "engine/dispatch")
    assert syncs
    for s in syncs:
        assert any(_inside(s, d) for d in decodes), s
        assert not any(_inside(s, pf) for pf in prefills), s
    both = 0
    for d in decodes:
        out = [ev for ev in dispatches if _inside(ev, d)]
        reads = [ev for ev in syncs if _inside(ev, d)]
        assert len(out) <= 1 and 0 < len(out) + len(reads) <= 3, d
        assert all(o[1] + o[2] <= r[1] for o in out for r in reads), d
        both += bool(out and reads)
    st = traced["eng"].stats()
    assert both > len(decodes) // 2 and 0 < st["steps_ahead"] < st["decode_steps"]
    assert st["rows_discarded"] == 0.0


def test_dispatch_events_keep_their_names_inside_dispatch_spans(traced):
    calls = [ev for evs in traced["lines"].values() for ev in evs if ev[0].startswith("PjitFunction(")]
    assert {ev[0] for ev in calls} == DISPATCHES
    dispatch = _named(traced, "engine/dispatch")
    for ev in calls:
        assert any(_inside(ev, d) for d in dispatch), ev
    for d in dispatch:  # and every dispatch span holds exactly one program's call
        assert len({ev[0] for ev in calls if _inside(ev, d)}) == 1, d


@pytest.mark.parametrize("program", ["decode", "prefill", "init"])
def test_lowered_modules_are_named(traced, program):
    eng, llm = traced["eng"], traced["llm"]
    S = eng.cfg.num_slots
    args = {
        "init": (),
        "decode": (llm.params, eng._pages, np.ascontiguousarray(eng.cache.tables), np.zeros(S, np.int32), np.zeros(S, np.int32), np.zeros(S, bool)),
        "prefill": (llm.params, eng._pages, np.ascontiguousarray(eng.cache.tables[0]), np.zeros(eng.cfg.prefill_chunk, np.int32), np.int32(0), np.int32(1)),
    }[program]
    name = {"init": "init_pages", "decode": "decode_step_paged", "prefill": "prefill_chunk_paged"}[program]
    text = eng._programs[program].lower(*args).as_text()
    assert f"module @jit_{name} " in text and "unknown" not in text.splitlines()[0]


def test_one_compile_each_after_the_traced_run(traced):
    assert traced["eng"].compile_stats() == {"prefill": 1, "decode": 1}
    assert traced["outs"] == traced["warm"]


@pytest.mark.parametrize("how", ["no_trace", "no_jax"])
def test_untraced_engine_gives_the_traced_tokens(traced, monkeypatch, how):
    """With no capture running a span is a TraceAnnotation that records
    nothing; in a process without jax it is the shared no-op.  Either way
    the engine's tokens are those of the traced run."""
    from ray_tpu.serve import tracing

    if how == "no_jax":
        monkeypatch.setattr(tracing, "sys", types.SimpleNamespace(modules={}))
        assert tracing.span("engine/iteration") is tracing._NO_SPAN
        with tracing.span("engine/iteration"), tracing.span("engine/admit"):
            pass  # re-entrant: one shared object nests
    else:
        assert type(tracing.span("engine/iteration")).__name__ == "TraceAnnotation"
    assert _serve(traced["eng"]) == traced["outs"]
