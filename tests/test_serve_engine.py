"""Continuous-batching inference engine (ray_tpu/serve/engine/):
page allocator, iteration-level scheduler, resident decode loop,
dag-channel token streaming, and the proxy's bounded-overload contract —
tiny model on CPU throughout."""

import time

import pytest

import ray_tpu
from _greedy import greedy_reference
from ray_tpu import serve
from ray_tpu.exceptions import EngineOverloadedError, EngineStreamError

pytestmark = pytest.mark.serve_engine


# --------------------------------------------------------- page allocator


def test_page_allocator_alloc_free_reuse():
    from ray_tpu.serve.engine import PageAllocator

    a = PageAllocator(num_pages=8, page_size=4)
    p1 = a.alloc(3)
    assert p1 == [0, 1, 2]  # lowest-first keeps the pool dense
    p2 = a.alloc(5)
    assert sorted(p2) == [3, 4, 5, 6, 7]
    assert a.alloc(1) is None  # exhausted: None, never an exception
    a.free(p1)
    assert a.available == 3
    p3 = a.alloc(2)
    assert set(p3) <= set(p1)  # freed pages are reused
    assert a.pages_for(9) == 3 and a.pages_for(1) == 1


def test_page_allocator_guards():
    from ray_tpu.serve.engine import PageAllocator

    a = PageAllocator(num_pages=4, page_size=4)
    pages = a.alloc(2)
    a.free(pages)
    with pytest.raises(ValueError):
        a.free(pages)  # double free
    with pytest.raises(ValueError):
        a.free([99])  # outside the pool


def test_page_allocator_fragmentation_and_compaction():
    from ray_tpu.serve.engine import PageAllocator

    a = PageAllocator(num_pages=8, page_size=4)
    held = [a.alloc(2) for _ in range(4)]  # pages 0..7
    assert a.fragmentation() == 0.0
    a.free(held[0])  # free 0,1
    a.free(held[2])  # free 4,5 -> two separate runs
    assert a.fragmentation() > 0.0
    allocated = held[1] + held[3]  # 2,3,6,7
    moves = a.compaction_plan(allocated)
    # plan relocates the allocated set onto ids 0..3
    assert sorted({d for _, d in moves} | (set(allocated) - {s for s, _ in moves})) == [
        0, 1, 2, 3,
    ]
    a.apply_compaction(4)
    assert a.fragmentation() == 0.0
    assert a.available == 4


def test_paged_cache_reserve_release():
    from ray_tpu.serve.engine import PagedKVCache

    c = PagedKVCache(num_slots=2, pages_per_slot=4, num_pages=6, page_size=4)
    assert c.reserve(0, 16)  # 4 pages
    assert not c.reserve(1, 12)  # 3 pages > 2 left: admission must wait
    assert c.reserve(1, 8)  # 2 pages fit
    assert (c.tables[0] >= 0).all()
    c.release(0)
    assert (c.tables[0] == -1).all()
    assert c.reserve(1, 16)  # grows in place after the release
    with pytest.raises(ValueError):
        c.reserve(1, 999)  # beyond the slot's logical span: a bug, not pressure


# ------------------------------------------------------------- scheduler


def _sched(slots=2, pages=8, page_size=4, max_queue=4):
    from ray_tpu.serve.engine import EngineScheduler, PagedKVCache

    cache = PagedKVCache(slots, 4, pages, page_size)
    return EngineScheduler(cache, max_queue=max_queue, prefill_chunk=2)


def test_scheduler_admit_retire_recycles_slots():
    s = _sched()
    r1 = s.submit([1, 2, 3], 4)
    r2 = s.submit([5], 4)
    r3 = s.submit([7, 8], 4)
    assert [r.rid for r in s.admit()] == [r1.rid, r2.rid]  # FCFS, 2 slots
    assert s.admit() == []  # no free slot for r3
    # prefill planning: FCFS, chunk-bounded
    req, start, toks = s.next_prefill()
    assert req is r1 and start == 0 and toks == [1, 2]
    assert not s.note_prefill(r1, 2)
    req, start, toks = s.next_prefill()
    assert req is r1 and start == 2 and toks == [3]
    assert s.note_prefill(r1, 1)  # prompt resident
    # retire r1 -> slot + pages recycle -> r3 admits
    s.retire(r1)
    assert r1.done and r1.slot == -1
    assert [r.rid for r in s.admit()] == [r3.rid]


def test_scheduler_eos_and_budget_retirement():
    s = _sched()
    (r,) = [s.submit([1], 3, eos_token=42)][:1]
    s.admit()
    assert not s.note_token(r, 7)
    assert s.note_token(r, 42)  # EOS retires before the budget
    assert r.out == [7, 42]
    r2 = s.submit([1], 2)
    s.admit()
    assert not s.note_token(r2, 1)
    assert s.note_token(r2, 1)  # budget retires


def test_scheduler_admission_blocked_not_crashed_on_page_pressure():
    s = _sched(slots=2, pages=2, page_size=4)  # pool covers ONE 2+4-token request
    r1 = s.submit([1, 2], 4)
    r2 = s.submit([3, 4], 4)
    assert [r.rid for r in s.admit()] == [r1.rid]  # r2 blocked on pages
    assert s.queue and s.queue[0] is r2
    s.retire(r1)
    assert [r.rid for r in s.admit()] == [r2.rid]  # unblocked by recycling


def test_scheduler_bounded_queue_overload():
    s = _sched(max_queue=2)
    s.submit([1], 2)
    s.submit([1], 2)
    with pytest.raises(EngineOverloadedError) as ei:
        s.submit([1], 2)
    assert ei.value.retry_after_s > 0
    with pytest.raises(ValueError):
        s.submit(list(range(100)), 100)  # beyond per-sequence capacity


# ------------------------------------------------------ stream transport


def test_stream_state_backpressure_sever_is_typed_on_pull_path():
    """A pull consumer that falls past the outbox bound must read a
    TYPED error frame — never a clean-looking truncated stream."""
    from ray_tpu.serve.engine.transport import StreamState

    st = StreamState(sid=1, outbox_limit=3)
    for i in range(3):
        st.emit({"t": [i], "done": False, "error": None})
    st.emit({"t": [99], "done": False, "error": None})  # over the bound: sever
    assert st.closed
    frames, done = st.pull(max_frames=16, timeout=1.0)
    assert done
    errs = [f for f in frames if f.get("error")]
    assert errs, "sever must surface as an error frame, not silent truncation"


def test_stream_hub_create_reaps_severed_streams():
    from ray_tpu.serve.engine import transport

    h = transport.StreamHub()
    st = h.create(outbox_limit=1)
    st.fail("test sever")
    st2 = h.create()
    assert h.get(st.sid) is None  # severed stream reaped on next create
    assert h.get(st2.sid) is st2


# ------------------------------------------------- engine loop (in-process)


def _tiny_llm():
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import ShardedLLM

    return ShardedLLM(
        LlamaConfig.tiny(compute_dtype=jnp.float32), tp=1, init="random"
    )


@pytest.fixture(scope="module")
def tiny_llm():
    return _tiny_llm()


def test_engine_mixed_lengths_match_the_plain_forward_one_shape(tiny_llm):
    """The tentpole invariant: concurrent sequences of different lengths
    produce exactly the tokens the plain whole-sequence forward produces
    for each alone, AND the whole run uses ONE compiled prefill shape +
    ONE compiled decode shape (no recompilation across the mix)."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    eng = InferenceEngine(
        tiny_llm,
        EngineConfig(
            num_slots=4, page_size=4, max_seq_len=48, prefill_chunk=4,
            max_new_tokens=6,
        ),
        deployment="t",
    )
    try:
        prompts = [[5, 7, 9], [3], list(range(1, 12)), [4, 4]]
        reqs = [eng.submit(p, 6) for p in prompts]
        outs = [r.sink.result(timeout=180) for r in reqs]
        for p, o in zip(prompts, outs):
            assert o == greedy_reference(tiny_llm.model, tiny_llm.params, p, 6)
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}
        # second wave re-uses recycled slots on the same programs
        r = eng.submit([9, 8, 7], 4)
        assert len(r.sink.result(timeout=60)) == 4
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}
    finally:
        eng.shutdown()


@pytest.mark.parametrize("table", ["two-blocks", "one-block"])
def test_engine_counts_how_far_the_context_walk_goes(table):
    """``ctx_blocks_walked / ctx_blocks_full``: under 1 while the fleet's
    contexts end in the first of a table's two blocks, 1 per call once one
    reaches the second, and 1 throughout where the table is one block."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from ray_tpu.serve.llm import ShardedLLM

    seq = {"two-blocks": 512, "one-block": 256}[table]
    llm = ShardedLLM(LlamaConfig.tiny(compute_dtype=jnp.float32, max_seq_len=seq), tp=1, init="random")
    eng = InferenceEngine(
        llm, EngineConfig(num_slots=2, page_size=16, max_seq_len=seq, prefill_chunk=64), deployment="t"
    )
    try:
        per_call = seq // 256
        eng.submit([5, 7, 9], 4).sink.result(timeout=120)
        st = eng.stats()
        # one chunk and three decode steps, each inside block 0
        assert (st["ctx_blocks_walked"], st["ctx_blocks_full"]) == (4.0, 4.0 * per_call)
        if table == "two-blocks":
            # chunks at 0..192 stay in block 0; the chunk at 256 (whose first
            # token is delivered from the prefill) and two decode steps reach block 1
            eng.submit(list(range(1, 231)) + list(range(1, 61)), 3).sink.result(timeout=120)
            now = eng.stats()
            assert now["ctx_blocks_walked"] - st["ctx_blocks_walked"] == 4 * 1 + 3 * 2
            assert now["ctx_blocks_full"] - st["ctx_blocks_full"] == 7 * 2
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}
    finally:
        eng.shutdown()


def test_engine_eos_truncates(tiny_llm):
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    eng = InferenceEngine(
        tiny_llm,
        EngineConfig(num_slots=2, page_size=4, max_seq_len=32, prefill_chunk=4),
        deployment="t",
    )
    try:
        full = eng.submit([5, 7, 9], 6).sink.result(timeout=120)
        eos = full[1]
        out = eng.submit([5, 7, 9], 6, eos_token=eos).sink.result(timeout=60)
        assert out == full[:2]  # stops AT the eos token
    finally:
        eng.shutdown()


def test_engine_admission_blocks_on_pool_pressure_then_completes(tiny_llm):
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    eng = InferenceEngine(
        tiny_llm,
        EngineConfig(
            num_slots=4, page_size=4, max_seq_len=16, num_pages=4,
            prefill_chunk=4, max_new_tokens=4,
        ),
        deployment="t",
    )
    try:
        # pool holds ~2 concurrent sequences; 6 requests must all finish
        # by waiting for recycled pages — blocked, never crashed
        reqs = [eng.submit([i + 1, i + 2], 4) for i in range(6)]
        outs = [r.sink.result(timeout=180) for r in reqs]
        assert all(len(o) == 4 for o in outs)
        st = eng.stats()
        assert st["requests_done"] == 6.0 and st["requests_failed"] == 0.0
        assert st["pages_used"] == 0.0  # everything recycled
    finally:
        eng.shutdown()


def test_engine_overload_is_typed_and_immediate(tiny_llm):
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    eng = InferenceEngine(
        tiny_llm,
        EngineConfig(
            num_slots=1, page_size=4, max_seq_len=16, num_pages=1,
            prefill_chunk=4, max_new_tokens=4, max_queue=2,
        ),
        deployment="t",
    )
    try:
        with pytest.raises(EngineOverloadedError):
            for _ in range(30):
                eng.submit([1, 2], 4)
    finally:
        eng.shutdown()


def test_engine_defrag_mid_flight_preserves_decode(tiny_llm):
    """Retiring interleaved sequences fragments the pool; compaction must
    relocate live pages without corrupting in-flight context."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    eng = InferenceEngine(
        tiny_llm,
        EngineConfig(
            num_slots=3, page_size=4, max_seq_len=32, prefill_chunk=4,
            max_new_tokens=16,
        ),
        deployment="t",
    )
    try:
        ref = greedy_reference(tiny_llm.model, tiny_llm.params, [5, 7, 9], 16)
        long_req = eng.submit([5, 7, 9], 16)
        short = [eng.submit([i + 1], 2) for i in range(2)]
        for r in short:
            r.sink.result(timeout=120)  # retire -> holes in the pool
        eng.defrag()
        out = long_req.sink.result(timeout=120)
        assert out == ref
    finally:
        eng.shutdown()


def test_engine_no_stamps_when_events_disabled(tiny_llm):
    """RAY_TPU_TASK_EVENTS=0 contract: no trace record exists, so the
    engine stamps nothing and ships nothing — one flag check."""
    from ray_tpu._private import task_events
    from ray_tpu.serve import tracing as serve_tracing
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    old = task_events.enabled
    task_events.set_enabled(False)
    try:
        assert serve_tracing.new_request("x") is None
        eng = InferenceEngine(
            tiny_llm,
            EngineConfig(num_slots=2, page_size=4, max_seq_len=16, prefill_chunk=4),
            deployment="t",
        )
        try:
            req = eng.submit([1, 2], 3, trace=serve_tracing.new_request("x"))
            assert req.trace is None
            assert len(req.sink.result(timeout=60)) == 3
            assert not serve_tracing._buf  # nothing buffered for shipping
        finally:
            eng.shutdown()
    finally:
        task_events.set_enabled(old)


def test_engine_tracing_stamps_and_single_seal(tiny_llm):
    """With events on, an engine request's record carries the engine
    stages and TTFT/TPOT, seals exactly once, and strips internal keys."""
    from ray_tpu._private import task_events
    from ray_tpu.serve import tracing as serve_tracing
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    old = task_events.enabled
    task_events.set_enabled(True)
    shipped = []
    orig_ship = serve_tracing._ship
    serve_tracing._ship = lambda batch: shipped.extend(batch)
    eng = InferenceEngine(
        tiny_llm,
        EngineConfig(num_slots=2, page_size=4, max_seq_len=16, prefill_chunk=4),
        deployment="t",
    )
    try:
        trace = serve_tracing.new_request("t")
        req = eng.submit([1, 2, 3], 4, trace=trace)
        req.sink.result(timeout=60)
        # the outer handler's finally must NOT have sealed (deferred)
        serve_tracing.finish_request(trace, error=False)
        serve_tracing.flush()
        assert len(shipped) == 1  # exactly one seal
        rec = shipped[0]
        ph = rec["phases"]
        for stage in (
            "serve_engine_submit", "serve_engine_admit", "serve_prefill_start",
            "serve_first_token", "serve_decode_end",
        ):
            assert stage in ph, stage
        assert (
            ph["serve_engine_submit"] <= ph["serve_engine_admit"]
            <= ph["serve_prefill_start"] <= ph["serve_first_token"]
        )
        # admitted, waiting for its first prefill chunk: a stage of its own
        assert task_events.durations(ph)["serve_prefill_wait"] >= 0.0
        assert rec["rid"] == req.rid  # joins the record to the engine's request
        assert rec["ttft_s"] is not None and rec["tpot_s"] is not None
        assert rec["tokens"] == 4
        assert not any(k.startswith("_") for k in rec)
    finally:
        serve_tracing._ship = orig_ship
        eng.shutdown()
        task_events.set_enabled(old)


# ------------------------------------------ one decode step in flight


class _StubLLM:
    """An engine's ``llm`` whose programs are Python: the chunk program
    answers the prompt's last token + 1 and the decode program each active
    row's input + 1, at once, as objects that log when the host READS them.
    The log holds the engine's dispatches, its reads and its spans in order."""

    class _Result:
        def __init__(self, log, tag, value):
            self.log, self.tag, self.value = log, tag, value

        def _read(self):
            if self.tag is not None:
                self.log.append(("read",) + self.tag)
            return self.value

        def __array__(self, dtype=None, copy=None):
            import numpy as np

            return np.asarray(self._read())

    def __init__(self, step_s=0.0):
        import types

        self.log, self.step_s = [], step_s
        self.cfg = types.SimpleNamespace(max_seq_len=4096)
        self.model = types.SimpleNamespace(pool_roles=lambda: ("pages", "pages"))
        self.params = None
        self.counts = {"prefill": 0, "decode": 0}

    def _out(self, kind, value):
        tag = (kind, self.counts[kind])
        self.counts[kind] += 1
        self.log.append(("dispatch",) + tag)
        return self._Result(self.log, tag, value)

    def engine_programs(self, *, num_pages, page_size, num_slots):
        import numpy as np

        def prefill(params, pages, table, chunk, start, n_valid, slot):
            return self._out("prefill", np.int32(chunk[n_valid - 1] + 1)), pages

        def decode(params, pages, tables, tokens, positions, active, join_slot, join_token):
            time.sleep(self.step_s)
            toks = np.array(tokens.value)
            if join_slot >= 0:
                toks[join_slot] = join_token.value
            return self._out("decode", np.where(active, toks + 1, -7).astype(np.int32)), pages

        return {
            "init": lambda: ("k", "v"),
            "prefill": prefill,
            "decode": decode,
            "place": lambda x: self._Result(self.log, None, x),
        }


def _in_flight(log):
    """Decode steps dispatched and not read, after each entry of the log."""
    n, out = 0, []
    for ev in log:
        if ev[1:2] == ("decode",):
            n += 1 if ev[0] == "dispatch" else -1
        out.append(n)
    return out


def test_engine_dispatches_the_next_step_before_it_reads_the_last(monkeypatch):
    """The order of a turn, on stub programs: decode step N+1 goes out before
    step N's tokens are read, a chunk's first token is read after the step
    it joins went out, and nothing is in flight while the loop idles, nor
    after ``shutdown``."""
    import contextlib
    import threading

    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from ray_tpu.serve.engine import loop as loop_mod

    llm = _StubLLM()

    @contextlib.contextmanager
    def span(name):
        llm.log.append(("span", name))
        yield
        llm.log.append(("end", name))

    monkeypatch.setattr(loop_mod, "span", span)

    class LoggedLock:
        """The engine's lock, each hold of it an entry of the log."""

        def __init__(self, lock):
            self.lock = lock

        def acquire(self):
            if threading.current_thread() is eng._thread:
                llm.log.append(("lock",))
            return self.lock.acquire()

        def release(self):
            self.lock.release()

        def __enter__(self):
            return self.acquire()

        def __exit__(self, *exc):
            self.release()

    cfg = EngineConfig(num_slots=2, page_size=4, max_seq_len=4096, prefill_chunk=4)
    eng = InferenceEngine(llm, cfg, deployment="t")
    eng._lock = LoggedLock(eng._lock)
    try:
        assert eng.submit([10, 11], 5).sink.result(timeout=30) == [12, 13, 14, 15, 16]
        # an EOS is learnt a step late: the step in flight then is read, and
        # its row discarded, before the loop idles
        assert eng.submit([20], 9, eos_token=23).sink.result(timeout=30) == [21, 22, 23]
        deadline = time.monotonic() + 10
        while eng.stats()["rows_discarded"] < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)  # a few idle turns
        log = list(llm.log)
        at = {ev: i for i, ev in enumerate(log)}
        steps = llm.counts["decode"]
        assert steps == 4 + 3  # budget 5: the chunk's token + 4 steps; EOS at the third token: 2 steps + 1 discarded
        for k in range(steps):
            assert ("read", "decode", k) in at, k
        ahead = [k for k in range(1, steps) if at[("dispatch", "decode", k)] < at[("read", "decode", k - 1)]]
        assert ahead == [1, 2, 3, 5, 6]  # every step but each request's first
        for j in range(2):  # the first token: read after the step its row joined went out
            joined = 0 if j == 0 else 4
            assert at[("dispatch", "prefill", j)] < at[("dispatch", "decode", joined)] < at[("read", "prefill", j)]
        flying = _in_flight(log)
        assert max(flying) == 2 and flying[-1] == 0
        idles = [i for i, ev in enumerate(log) if ev == ("span", "engine/idle")]
        assert idles and all(flying[i] == 0 for i in idles)
        # a delivery notes and retires under ONE hold of the lock, however many
        # rows; the acquisition alone is engine/lock, the pass over the sinks engine/emit
        delivers = [i for i, ev in enumerate(log) if ev == ("span", "engine/deliver")]
        assert len(delivers) == steps + 2  # a read a step, and a first token a request
        whole = [("span", "engine/lock"), ("lock",), ("end", "engine/lock"), ("span", "engine/emit"), ("end", "engine/emit"), ("end", "engine/deliver")]
        for i in delivers:
            assert log[i + 1 : i + 7] == whole, log[i : i + 8]
        st = eng.stats()
        assert (st["decode_steps"], st["steps_ahead"], st["rows_discarded"]) == (7.0, 5.0, 1.0)
        assert st["tokens_generated"] == 8.0  # the discarded row is not counted
    finally:
        eng.shutdown()

    # shutdown with a step in flight: the device is waited for, the caller fails typed
    llm = _StubLLM(step_s=0.002)
    eng = InferenceEngine(llm, cfg, deployment="t")
    req = eng.submit([1], 4000)
    deadline = time.monotonic() + 10
    while llm.counts["decode"] < 5 and time.monotonic() < deadline:
        time.sleep(0.001)
    eng.shutdown()
    assert not eng._thread.is_alive()
    assert llm.counts["decode"] >= 5 and _in_flight(llm.log)[-1] == 0
    with pytest.raises(EngineStreamError):
        req.sink.result(timeout=5)


def _eos_cut(full):
    """(eos token, the answer it cuts ``full`` to): the first token past the
    first that has not appeared before it."""
    k = next(i for i in range(1, len(full) - 1) if full[i] not in full[:i])
    return full[k], full[: k + 1]


@pytest.mark.parametrize("tp", [1, 2])
def test_engine_mixed_fleet_with_an_eos_matches_each_request_alone(tp):
    """Lengths, budgets and an EOS in the middle of the fleet: every request
    answers, token for token, what the plain forward gives it alone; the
    request queued behind two slots takes the EOS request's slot and answers
    its own tokens too.  A budget never costs a discarded row, an EOS one."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from ray_tpu.serve.llm import ShardedLLM

    llm = ShardedLLM(LlamaConfig.tiny(compute_dtype=jnp.float32), tp=tp, init="random")
    ref = lambda p, n: greedy_reference(llm.model, llm.params, p, n)  # noqa: E731
    eng = InferenceEngine(
        llm, EngineConfig(num_slots=2, page_size=4, max_seq_len=48, prefill_chunk=4), deployment="t"
    )
    try:
        jobs = [([5, 7, 9], 6), ([3], 1), (list(range(1, 12)), 9), ([4, 4], 2), (list(range(20, 29)), 4)]
        reqs = [eng.submit(p, n) for p, n in jobs]
        for (p, n), r in zip(jobs, reqs):
            assert r.sink.result(timeout=180) == ref(p, n), (p, n)
        st = eng.stats()
        assert st["rows_discarded"] == 0.0 and 0 < st["steps_ahead"] < st["decode_steps"]

        long_p, eos_p, queued_p = [9, 8, 7, 6, 5], [5, 7, 9], [2, 4, 6, 8]
        eos, cut = _eos_cut(ref(eos_p, 10))
        slots, pages = [], {}
        admit = eng.sched.admit

        def admit_and_note():
            got = admit()
            for r in got:
                slots.append((r.rid, r.slot))
                pages[r.rid] = set(eng.cache.slot_pages(r.slot))
            return got

        eng.sched.admit = admit_and_note
        a = eng.submit(long_p, 14)
        b = eng.submit(eos_p, 10, eos_token=eos)
        c = eng.submit(queued_p, 7)
        assert b.sink.result(timeout=180) == cut
        assert a.sink.result(timeout=180) == ref(long_p, 14)
        assert c.sink.result(timeout=180) == ref(queued_p, 7)
        # the slot and the pages the EOS freed, with a row of their old owner
        # still in flight over them, went to the request queued behind
        assert dict(slots)[c.rid] == dict(slots)[b.rid] and pages[c.rid] & pages[b.rid]
        now = eng.stats()
        assert now["rows_discarded"] == 1.0  # one ending on a stop token, one row in flight then
        assert now["steps_ahead"] > st["steps_ahead"]
        assert now["requests_failed"] == 0.0 and now["pages_used"] == 0.0
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}
    finally:
        eng.shutdown()


def test_engine_cancel_defrag_and_new_weights_with_a_step_in_flight(tiny_llm):
    """A sink that holds the engine thread inside a delivery — so with the
    next step already in flight — while a neighbour is cancelled, a defrag
    parked and the same weights staged again: the held request's answer is
    its answer alone, the pages really moved, and both programs are the two
    that compiled."""
    import threading

    import jax

    from ray_tpu.serve.engine import BufferSink, EngineConfig, InferenceEngine

    class GateSink(BufferSink):
        def __init__(self, at):
            super().__init__()
            self.at, self.reached, self.go = at, threading.Event(), threading.Event()

        def emit(self, frame):
            super().emit(frame)
            if len(self.tokens) == self.at:
                self.reached.set()
                assert self.go.wait(60)

    eng = InferenceEngine(
        tiny_llm,
        EngineConfig(num_slots=3, page_size=4, max_seq_len=48, prefill_chunk=4),
        deployment="t",
    )
    try:
        class FramesSink(BufferSink):
            def __init__(self):
                super().__init__()
                self.frames = []

            def emit(self, frame):
                self.frames.append((list(frame["t"]), frame["done"], frame["error"]))
                super().emit(frame)

        gate = GateSink(at=6)
        short = eng.submit([1], 2)  # admitted first: the lowest pages, free again by the gate
        held = eng.submit([5, 7, 9], 30, sink=gate)
        other = eng.submit([2, 4], 30, sink=FramesSink())
        assert gate.reached.wait(120)
        assert eng._ahead is not None and len(short.sink.result(timeout=5)) == 2
        # a sink that blocks holds the engine thread, not the engine's lock
        seen = {}
        asker = threading.Thread(target=lambda: seen.update(eng.stats()))
        asker.start()
        asker.join(10)
        assert not asker.is_alive() and seen["steps_ahead"] > 0
        assert any(req is other for req, _ in eng._ahead[1])  # a token of it is in flight
        eng.cancel(other)
        before = len(other.sink.frames)
        eng.update_weights(jax.tree.map(lambda x: x + 0, tiny_llm.params))
        moved = {}
        parked = threading.Thread(target=lambda: moved.update(eng.defrag(timeout=120)))
        parked.start()
        gate.go.set()
        assert held.sink.result(timeout=180) == greedy_reference(tiny_llm.model, tiny_llm.params, [5, 7, 9], 30)
        parked.join(120)
        assert moved["moves"] > 0
        got = other.sink.result(timeout=60)
        assert 0 < len(got) < 30 and got == greedy_reference(tiny_llm.model, tiny_llm.params, [2, 4], len(got))
        # after the cancel: the rest of the delivery the gate interrupted (its
        # row comes after the held one), then the final frame, empty; the
        # token in flight at the cancel went nowhere
        assert [(len(t), done, err) for t, done, err in other.sink.frames[before:]] == [(1, False, None), (0, True, None)]
        assert [done for _, done, _ in other.sink.frames].count(True) == 1
        st = eng.stats()
        assert eng.weight_updates == 1 and st["rows_discarded"] >= 1.0
        assert st["requests_failed"] == 0.0 and st["slots_active"] == 0.0
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}
    finally:
        eng.shutdown()


def test_engine_routing_totals_stay_exact_with_a_step_in_flight():
    """An expert model's routing counter is read at the gauge tick, here every
    turn, each time behind the step in flight: the totals are what the
    device computed, to the assignment -- every prompt token and every
    generated token but a request's last, a discarded row included, through
    2 layers x 2 experts -- and no reading ever goes back."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from ray_tpu.serve.llm import ShardedLLM

    cfg = LlamaConfig(
        vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=4, hidden_dim=32, max_seq_len=128,
        n_experts=8, n_experts_per_tok=2, qk_norm=True, compute_dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
    )
    llm = ShardedLLM(cfg, tp=1, init="random")
    eng = InferenceEngine(
        llm, EngineConfig(num_slots=3, page_size=4, max_seq_len=48, prefill_chunk=4, gauge_period_s=0.0), deployment="t"
    )
    try:
        eos, cut = _eos_cut(greedy_reference(llm.model, llm.params, [5, 7, 9], 12))
        jobs = [([5, 7, 9], 12, eos), (list(range(1, 12)), 9, None), ([4, 4], 6, None)]
        reqs = [eng.submit(p, n, eos_token=e) for p, n, e in jobs]
        readings = []
        while not all(r.done for r in reqs):
            readings.append(eng.stats().get("moe_assignments", 0.0))
        outs = [r.sink.result(timeout=180) for r in reqs]
        assert outs[0] == cut and [len(o) for o in outs[1:]] == [9, 6]
        eng._wake.set()
        time.sleep(0.3)  # an idle tick reads the counter with nothing in flight
        st = eng.stats()
        assert readings == sorted(readings) and readings[-1] <= st["moe_assignments"]
        assert st["rows_discarded"] == 1.0 and st["steps_ahead"] > 0
        rows = sum(len(p) + len(o) - 1 for (p, _, _), o in zip(jobs, outs)) + int(st["rows_discarded"])
        assert st["moe_assignments"] == rows * 2 * 2 == sum(st["moe_expert_load"])
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}
    finally:
        eng.shutdown()


# --------------------------------------------------------- serve e2e paths


@pytest.fixture(scope="module")
def engine_cluster():
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve import llm as llm_mod

    ray_tpu.init(num_cpus=4)
    cfg = LlamaConfig(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        vocab_size=256, compute_dtype=jnp.float32, max_seq_len=64,
    )
    dep = llm_mod.engine_llm_deployment(
        cfg, new_tokens=6, num_slots=4, page_size=4, prefill_chunk=4,
        max_queue=8, num_tpus=0, tp=1, name="llm",
    )
    handle = serve.run(dep.bind())
    ray_tpu.get(handle.remote(5), timeout=600)  # warm the compile
    yield cfg, handle
    serve.shutdown()
    ray_tpu.shutdown()


def test_engine_deployment_buffered_and_mixed(engine_cluster):
    _, handle = engine_cluster
    out = ray_tpu.get(handle.remote(5), timeout=120)
    assert len(out) == 6
    refs = [
        handle.remote({"prompt": list(range(1, n + 1)), "max_new_tokens": 5})
        for n in (1, 3, 9, 2)
    ]
    outs = ray_tpu.get(refs, timeout=300)
    assert all(len(o) == 5 for o in outs)
    stats = ray_tpu.get(
        serve.get_deployment_handle("llm").method("engine_stats").remote(),
        timeout=60,
    )
    assert stats["compile_prefill"] == 1.0 and stats["compile_decode"] == 1.0


def test_engine_deployment_three_prompt_forms(engine_cluster):
    """A seed, a list of ids and a dict are one request in three shapes
    (``_parse_prompt_spec``): through ``serve.run`` and the handle they
    answer the same tokens, the dict's own budget and ids beyond the
    vocabulary included, and ``info()`` reports the sharded model."""
    from ray_tpu.serve.llm import _parse_prompt_spec

    cfg, handle = engine_cluster
    assert _parse_prompt_spec(5, 256, 6) == ([5], 6, None)
    assert _parse_prompt_spec([5, 261], 256, 6) == ([5, 5], 6, None)
    assert _parse_prompt_spec({"prompt": 5, "max_new_tokens": 3, "eos_token": 9}, 256, 6) == ([5], 3, 9)
    seed, ids, spec, wrapped, short = ray_tpu.get(
        [
            handle.remote(5),
            handle.remote([5]),
            handle.remote({"prompt": [5]}),
            handle.remote(5 + cfg.vocab_size),
            handle.remote({"prompt": 5, "max_new_tokens": 3}),
        ],
        timeout=300,
    )
    assert len(seed) == 6 and seed == ids == spec == wrapped
    assert short == seed[:3]
    info = ray_tpu.get(
        serve.get_deployment_handle("llm").method("info").remote(), timeout=60
    )
    assert info["tp"] == 1 and info["shards"]["total_bytes"] > 0


def test_engine_deployment_info_and_fetched_request_records(engine_cluster):
    """info() names the replica's devices, and a request's sealed record
    comes back whole from the head: the engine's rid, every stamp, and
    the admitted-to-prefill wait as a stage of its own."""
    from ray_tpu.experimental.state.api import summarize_workloads

    _, handle = engine_cluster
    info = ray_tpu.get(
        serve.get_deployment_handle("llm").method("info").remote(), timeout=60
    )
    assert info["platform"] == "cpu" and info["device_kind"]
    assert info["device_count"] >= 1 and info["peak_bytes_in_use"] >= 0
    ray_tpu.get(
        [handle.remote({"prompt": [7] * n, "max_new_tokens": 3}) for n in (2, 6, 9)],
        timeout=120,
    )
    records = []
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline:
        reply = summarize_workloads("serve", limit=50)
        records = [r for r in reply.get("records", []) if r["name"] == "serve:llm"]
        if len([r for r in records if r.get("rid") is not None]) >= 3:
            break
        time.sleep(0.2)
    engine_records = [r for r in records if r.get("rid") is not None]
    assert len(engine_records) >= 3, reply
    assert len({r["rid"] for r in engine_records}) == len(engine_records)
    for rec in engine_records:
        assert rec["durations"]["serve_prefill_wait"] >= 0.0
        assert rec["phases"]["serve_engine_admit"] <= rec["phases"]["serve_prefill_start"]
    stages = {row["stage"] for row in reply["summary"] if row["deployment"] == "llm"}
    assert "serve_prefill_wait" in stages


def test_stream_tokens_incremental_and_ordered(engine_cluster):
    _, handle = engine_cluster
    frames = []
    for f in handle.stream_tokens({"prompt": [1, 2, 3], "max_new_tokens": 8}):
        frames.append(f)
    toks = [t for fr in frames for t in fr]
    assert len(toks) == 8
    # incrementality: tokens arrived as multiple frames, not one blob
    assert len(frames) >= 2
    # order + content match the buffered path exactly
    out = ray_tpu.get(
        handle.remote({"prompt": [1, 2, 3], "max_new_tokens": 8}), timeout=120
    )
    assert out == toks


def test_stream_tokens_pull_fallback(engine_cluster, monkeypatch):
    """With the direct transport unavailable the same stream flows over
    the actor-call pull path."""
    from ray_tpu.serve.engine import transport

    def _no_transport(*a, **k):
        raise EngineStreamError("transport disabled for test")

    monkeypatch.setattr(transport, "open_token_stream", _no_transport)
    toks = [
        t
        for fr in handle_stream(engine_cluster)
        for t in fr
    ]
    assert len(toks) == 5


def handle_stream(engine_cluster):
    _, handle = engine_cluster
    return handle.stream_tokens({"prompt": [2, 4], "max_new_tokens": 5})


def test_stream_abandon_releases_engine_slot(engine_cluster):
    _, handle = engine_cluster
    it = handle.stream_tokens({"prompt": [1, 2], "max_new_tokens": 6})
    next(it)  # first frame only
    it.close()  # abandon mid-stream
    # the engine must retire the request and free its slot
    deadline = time.time() + 30
    while time.time() < deadline:
        stats = ray_tpu.get(
            serve.get_deployment_handle("llm").method("engine_stats").remote(),
            timeout=60,
        )
        if stats["slots_active"] == 0.0:
            break
        time.sleep(0.2)
    assert stats["slots_active"] == 0.0


def test_summary_serve_reports_ttft_and_engine_gauges(engine_cluster):
    from ray_tpu.experimental.state import summarize_workloads

    _, handle = engine_cluster
    ray_tpu.get(handle.remote({"prompt": [3, 1], "max_new_tokens": 4}), timeout=120)
    deadline = time.time() + 30
    s = {}
    while time.time() < deadline:
        s = summarize_workloads("serve")
        if s.get("ttft", {}).get("llm") and "llm" in (s.get("engine") or {}):
            break
        time.sleep(0.5)
    assert s.get("ttft", {}).get("llm"), "TTFT percentiles missing from summary serve"
    eng = s["engine"]["llm"]
    assert "kv_pages:total" in eng and eng["kv_pages:total"] > 0
    assert "slots:total" in eng
    mem = summarize_workloads("memory")
    assert "llm" in (mem.get("serve_engine") or {})


@pytest.mark.chaos
def test_replica_kill_mid_stream_typed_error(engine_cluster):
    """A killed replica mid-stream must surface EngineStreamError at the
    consumer — typed, prompt, never a hang."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve import llm as llm_mod

    cfg = LlamaConfig(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        vocab_size=256, compute_dtype=jnp.float32, max_seq_len=512,
    )
    dep = llm_mod.engine_llm_deployment(
        cfg, new_tokens=256, num_slots=2, page_size=16, prefill_chunk=16,
        num_tpus=0, tp=1, name="llm_kill",
    )
    handle = serve.run(dep.bind())
    idx, replica = handle._pick_replica()
    it = handle.stream_tokens({"prompt": [1, 2, 3], "max_new_tokens": 256})
    got = next(it)  # stream is live
    assert got
    ray_tpu.kill(replica)
    with pytest.raises(EngineStreamError):
        deadline = time.time() + 60
        while time.time() < deadline:
            next(it)
    serve.delete("llm_kill")


def test_proxy_sse_streams_and_503_sheds(engine_cluster):
    """HTTP surface: SSE token streaming end to end (first frame before
    the generation completes is covered by the handle test; here the wire
    format + done event), and a full admission queue answers 503 with
    Retry-After instead of queueing unboundedly."""
    import json
    import urllib.error
    import urllib.request

    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve import llm as llm_mod

    cfg = LlamaConfig(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        vocab_size=256, compute_dtype=jnp.float32, max_seq_len=512,
    )
    dep = llm_mod.engine_llm_deployment(
        cfg, new_tokens=8, num_slots=1, page_size=16, prefill_chunk=16,
        max_queue=1, num_tpus=0, tp=1, name="llm_http",
    )
    handle = serve.run(dep.bind())
    url = serve.start_http_proxy(0)
    try:
        ray_tpu.get(handle.remote(1), timeout=600)  # warm

        # SSE: incremental data frames then the done event
        req = urllib.request.Request(
            f"{url}/llm_http?stream=sse",
            data=json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 6}).encode(),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.headers["Content-Type"].startswith("text/event-stream")
            body = r.read().decode()
        data_frames = [l for l in body.splitlines() if l.startswith("data: {\"t\"")]
        toks = [t for l in data_frames for t in json.loads(l[len("data: "):])["t"]]
        assert len(toks) == 6
        assert "event: done" in body

        # overload: saturate the single slot + 1-deep queue with slow
        # requests, then expect a bounded 503 rejection
        slow = {"prompt": [1, 2], "max_new_tokens": 400}
        refs = []
        saw_503 = False
        deadline = time.time() + 60
        while time.time() < deadline and not saw_503:
            # topped up every round: a slow request is 400 steps of a tiny
            # model, which the engine may finish before the proxy's request lands
            refs += [handle.remote(slow) for _ in range(4)]
            try:
                urllib.request.urlopen(
                    urllib.request.Request(
                        f"{url}/llm_http",
                        data=json.dumps({"prompt": [5], "max_new_tokens": 4}).encode(),
                        headers={"Content-Type": "application/json"},
                    ),
                    timeout=120,
                )
            except urllib.error.HTTPError as e:
                if e.code == 503:
                    saw_503 = True
                    assert int(e.headers["Retry-After"]) >= 1
                    break
                raise
        assert saw_503, "full admission queue must shed with 503"
        ray_tpu.wait(refs, num_returns=len(refs), timeout=600)
    finally:
        serve.delete("llm_http")
