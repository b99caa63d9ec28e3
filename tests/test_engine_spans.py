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


# ---- PR 38: the lock's acquisition and the sinks' pass inside a delivery, the
# thread's own clocks, and the gauges' publisher off the engine thread


@pytest.mark.parametrize("child,parent", [
    ("engine/lock", "engine/deliver"), ("engine/emit", "engine/deliver"),
    ("engine/lock", "engine/admit"), ("engine/lock", "engine/iteration"),
])
def test_lock_and_emit_lie_where_they_claim(traced, child, parent):
    children, parents = _named(traced, child), _named(traced, parent)
    assert children and parents
    for p in parents:  # a delivery takes the lock once and emits once; an admission and a turn take it at least once
        held = [c for c in children if _inside(c, p)]
        assert len(held) == 1 if parent == "engine/deliver" else held, p
    turns = _named(traced, "engine/iteration")
    assert all(any(_inside(c, t) for t in turns) for c in children)  # never from an idle turn
    if child == "engine/emit":
        assert all(any(_inside(c, p) for p in parents) for c in children)
        for p in parents:  # the lock is given up before the sinks are walked
            (lock,) = [c for c in _named(traced, "engine/lock") if _inside(c, p)]
            (emit,) = [c for c in children if _inside(c, p)]
            assert lock[1] + lock[2] <= emit[1]


def test_a_lock_span_is_the_acquisition_alone(traced):
    locks = _named(traced, "engine/lock")
    others = [ev for ev in traced["spans"] if ev[0] != "engine/lock"]
    for lock in locks:
        assert not any(_inside(ev, lock) for ev in others), lock
    # three a turn (admit, next_prefill, and one a delivery): a turn delivers at most twice
    per_turn = [sum(_inside(c, t) for c in locks) for t in _named(traced, "engine/iteration")]
    assert min(per_turn) >= 2 and max(per_turn) <= 4, sorted(set(per_turn))


def test_the_threads_own_clocks_order_and_only_grow(traced):
    """``stats()`` from more threads than cores while the engine serves, the
    interpreter switching threads every 10 us: in EVERY reply, a turn under
    way or not, the turn's time covers its waits, and no clock runs back."""
    import sys
    import threading

    eng = traced["eng"]
    seen, stop = [], threading.Event()

    def ask():
        mine = [eng.stats()]
        while not stop.is_set():
            mine.append(eng.stats())
        seen.append(mine)

    askers = [threading.Thread(target=ask) for _ in range((os.cpu_count() or 4) + 2)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in askers:
            t.start()
        first = eng.stats()
        assert _serve(eng) == traced["outs"]
        last = eng.stats()
    finally:
        stop.set()
        for t in askers:
            t.join(30)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in askers) and len(seen) == len(askers)
    for mine in seen:
        for a, b in zip(mine, mine[1:]):
            for key in ("turn_s", "sync_wait_s", "lock_wait_s", "iterations"):
                assert b[key] >= a[key] >= 0.0, key
        for st in mine:
            assert st["turn_s"] >= st["sync_wait_s"] + st["lock_wait_s"], st
    assert last["turn_s"] > first["turn_s"] and last["sync_wait_s"] > first["sync_wait_s"]
    # an idle loop's ticks and wake waits are no turn's time
    time.sleep(0.1)
    idle = eng.stats()
    time.sleep(0.1)
    assert eng.stats()["turn_s"] == idle["turn_s"] and eng.stats()["sync_wait_s"] == idle["sync_wait_s"]


def test_a_held_lock_is_lock_wait_and_not_sync_wait(traced):
    import threading

    from ray_tpu.serve.engine import BufferSink

    eng = traced["eng"]
    holding = threading.Event()

    def hold():
        with eng._lock:
            holding.set()
            time.sleep(0.05)

    holder = threading.Thread(target=hold)

    class Sink(BufferSink):
        def emit(self, frame):  # engine thread, lock released: the next acquisition of its turn finds it taken
            super().emit(frame)
            if not holder.ident:
                holder.start()
                assert holding.wait(5)

    before = eng.stats()
    assert len(eng.submit([5, 7, 9], BUDGET, sink=Sink()).sink.result(timeout=180)) == BUDGET
    holder.join()
    after = eng.stats()
    d = {k: after[k] - before[k] for k in ("turn_s", "sync_wait_s", "lock_wait_s")}
    assert d["lock_wait_s"] >= 0.03, d
    assert d["turn_s"] >= d["sync_wait_s"] + d["lock_wait_s"], d  # disjoint: the 50 ms is counted once


def test_stats_walks_the_free_list_with_no_lock_held(traced, monkeypatch):
    import threading

    from ray_tpu.serve.engine import kv_cache
    from ray_tpu.serve.engine import loop as loop_mod

    eng = traced["eng"]
    walks = []

    def probing(free):
        got = []

        def probe():  # another thread can take both locks while the walk runs
            for lock in (eng._lock, eng.cache._lock):
                ok = lock.acquire(timeout=2)
                got.append(ok)
                if ok:
                    lock.release()

        t = threading.Thread(target=probe)
        t.start()
        t.join()
        walks.append(got)
        return kv_cache.fragmentation_of(free)

    def refuse(self):
        raise AssertionError("stats() walked the allocator's own list")

    monkeypatch.setattr(loop_mod, "fragmentation_of", probing)
    monkeypatch.setattr(kv_cache.PageAllocator, "fragmentation", refuse)
    out = []
    caller = threading.Thread(target=lambda: out.append(eng.stats()))
    caller.start()
    caller.join(10)
    assert out and 0.0 <= out[0]["fragmentation"] <= 1.0
    assert [True, True] in walks


def _fragmentation_by_the_loop(ids):
    """The walk as it was written before numpy took it: the reference."""
    if len(ids) <= 1:
        return 0.0
    ordered = sorted(ids)
    longest = run = 1
    for a, b in zip(ordered, ordered[1:]):
        run = run + 1 if b == a + 1 else 1
        longest = max(longest, run)
    return 1.0 - longest / len(ids)


@pytest.mark.parametrize("ids,expected", [
    ([], 0.0), ([7], 0.0), ([3, 2, 1, 0], 0.0), ([9, 8, 5, 4, 3, 0], 0.5), ([0, 2, 4, 6], 0.75),
    (list(range(100, 40, -1)) + [7, 5], 1 - 60 / 62), ("random", None),
])
def test_fragmentation_of_a_copy_is_the_allocators_own(ids, expected):
    from ray_tpu.serve.engine.kv_cache import PageAllocator, fragmentation_of

    cases = [ids]
    if ids == "random":  # the pool of the largest cell, free lists of every density
        rng = np.random.default_rng(38)
        cases = [rng.choice(16384, size=n, replace=False).tolist() for n in (2, 3, 100, 5000, 16000, 16384)]
    for case in cases:
        want = _fragmentation_by_the_loop(case)
        assert expected is None or want == pytest.approx(expected)
        assert fragmentation_of(case) == want  # the same integers divided: equal, not close
        a = PageAllocator(16384, 4)
        a._free = sorted(case, reverse=True)
        assert a.fragmentation() == want


def test_the_engine_thread_writes_no_gauge(traced, monkeypatch):
    """With a connected worker every write of a gauge is a blocking round
    trip to the head: all ten series a tick come from the publisher thread,
    none from ``engine-<deployment>``."""
    import threading

    from ray_tpu._private import worker as worker_mod
    from ray_tpu.util import metrics

    writes = []

    def store(self, value, tags, mode):
        kind = (tags or {}).get("kind")
        writes.append((threading.current_thread().name, self.name + (f":{kind}" if kind else ""), value))

    monkeypatch.setattr(worker_mod, "_require_connected", lambda: None)
    monkeypatch.setattr(metrics.Metric, "_store", store)
    eng = traced["eng"]
    assert _serve(eng) == traced["outs"]
    deadline = time.monotonic() + 10  # a period is 10 ms: until a tick in which no turn ran
    while time.monotonic() < deadline and not any(w[1:] == ("ray_tpu_serve_engine_host_share", 0.0) for w in writes[-10:]):
        time.sleep(0.01)
    with eng._publish_lock:  # between two ticks
        monkeypatch.undo()
    assert writes
    assert {w[0] for w in writes} == {"gauges-spans"}, {w[0] for w in writes}
    assert eng._thread.name == "engine-spans" and eng._publisher.name == "gauges-spans"
    series = {w[1] for w in writes}
    assert series == {
        "ray_tpu_serve_engine_slots:active", "ray_tpu_serve_engine_slots:decode", "ray_tpu_serve_engine_slots:prefill",
        "ray_tpu_serve_engine_slots:total", "ray_tpu_serve_engine_queue_depth", "ray_tpu_serve_engine_kv_pages:used",
        "ray_tpu_serve_engine_kv_pages:total", "ray_tpu_serve_engine_page_fragmentation", "ray_tpu_serve_engine_host_share",
        "ray_tpu_serve_engine_tokens_total", "ray_tpu_serve_engine_cache_bytes_per_position",
    }
    # fixed when the pool was made: written once, when the gauges are made, not a round trip a period
    assert [w[2] for w in writes if w[1] == "ray_tpu_serve_engine_cache_bytes_per_position"] == [eng.stats()["cache_bytes_per_position"]]
    shares = [w[2] for w in writes if w[1] == "ray_tpu_serve_engine_host_share"]
    assert all(0.0 <= s <= 1.0 for s in shares) and any(s > 0.0 for s in shares) and shares[-1] == 0.0  # idle at the end
    # the counter's increments add up to every token the engine has generated, the first publish catching up
    assert sum(w[2] for w in writes if w[1] == "ray_tpu_serve_engine_tokens_total") == eng.stats()["tokens_generated"]


def test_the_publisher_ends_with_the_engine_and_threads_do_not_pile_up(traced):
    import threading

    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    before = threading.active_count()
    for _ in range(3):
        eng = InferenceEngine(traced["llm"], EngineConfig(num_slots=2, page_size=4, max_seq_len=16, prefill_chunk=4), deployment="brief")
        names = {t.name for t in threading.enumerate()}
        assert {"engine-brief", "gauges-brief"} <= names
        eng.shutdown()
        assert not eng._thread.is_alive() and not eng._publisher.is_alive()
    assert threading.active_count() == before
    assert not {"engine-brief", "gauges-brief"} & {t.name for t in threading.enumerate()}
