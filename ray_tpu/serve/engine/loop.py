"""The resident decode loop: one engine thread per replica, one jitted
step per iteration.

Per PAPERS.md §2 (Pathways) the scarce resource on a single-controller
TPU runtime is per-step DISPATCH latency, so the engine is a loop that
lives inside the replica actor and whose host work per token step is
near zero: build three small arrays, call ONE pre-compiled program
over the tp mesh (active-slot masking covers empty slots), read S int32s
back.  The read is what makes per-request TTFT/TPOT real measurements
and feeds every stream its next frame; batching it per step (not per
request) is what keeps the loop O(1) in concurrency.

The loop keeps ONE decode step in flight.  The token frontier — what the
next step decodes from — stays on the device (the step before's result,
with the row that joins this turn taking its chunk's sampled token inside
the program), so step N+1 is dispatched before step N's tokens are read,
and everything the host does with them — the read, bookkeeping,
delivery, flushes, the next admission and build — runs under a
program that is already queued.  Counts are all a step's other arguments
need, and the host has them without reading a token:
``len(out) + unread``.  The depth is one and fixed: there is no other
order of a turn and no switch (DESIGN.md, "One step in flight").

Iteration shape (scheduler.py decides, this module executes):

    admit  →  [dispatch one prefill chunk]  →  [dispatch decode step N+1]
           →  read step N: deliver frames, retire / recycle slots
           →  [read the chunk's first token: deliver]

Inside a profiler capture the same shape reads as spans on this thread
(``serve/tracing.py span``, vocabulary ``ENGINE_SPANS``), on the device
trace's clock:

    engine/iteration
      engine/admit     engine/lock                                     (reap, admit)
      engine/lock                                                      (next_prefill)
      engine/prefill   engine/build → engine/dispatch                  (the chunk)
      engine/decode    [engine/build → engine/dispatch]                (step N+1)
                       [engine/sync → engine/deliver]                  (step N)
                       [engine/sync → engine/deliver]                  (a first token)
                         engine/deliver = engine/lock → bookkeeping under the lock → engine/emit
      engine/flush     (only with laggard streams)
      engine/sync      (twice a second, a model with a routing counter: its read, which waits for step N+1)
      engine/gauges    (twice a second: what is left of the tick on this thread, the fold of that read)
    engine/idle        (the wake wait of a turn with no work)

``engine/lock`` is the ACQUISITION of the engine's lock and nothing else
(the time work waited for ``submit()`` or a ``stats()`` caller on another
thread); ``engine/emit`` is the pass over the sinks with the lock released.
The thread also keeps three clocks of its own, whole window and no
profiler (``stats()``): ``turn_s``, ``sync_wait_s``, ``lock_wait_s``.

Nothing on this thread talks to the head: token frames leave through
delivery sinks (buffered result, or dag-channel streams via
engine/transport.py), observability leaves through the serve tracer's
batched SERVE_TRACE frames, and the ``ray_tpu_serve_engine_*`` gauges are
written by a publisher thread of the engine's own (``gauges-<deployment>``:
one ``stats()`` and ten blocking writes to the head every
``gauge_period_s``, which this thread used to pay for).
"""

from __future__ import annotations

import contextlib
import math
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.exceptions import EngineStreamError
from ray_tpu.serve import tracing as serve_tracing
from ray_tpu.serve.tracing import span
from ray_tpu.serve.engine.kv_cache import PagedKVCache, fragmentation_of
from ray_tpu.serve.engine.scheduler import (
    DECODE,
    EngineRequest,
    EngineScheduler,
)
from ray_tpu.tools import graftsan
from ray_tpu.util.lockwitness import named_lock, named_rlock

__all__ = ["EngineConfig", "InferenceEngine", "BufferSink"]


@dataclass(frozen=True)
class EngineConfig:
    """Engine geometry.  Every field here shapes a jitted program or a
    pool size — all of them are fixed at engine construction (the
    jit-shape invariant); only ``max_queue`` may be reconfigured live."""

    num_slots: int = 8  # concurrent sequences per replica
    page_size: int = 16  # tokens per KV page
    max_seq_len: int = 256  # per-sequence logical capacity (prompt + generated)
    # physical pool size; 0 = full residency (num_slots * pages_per_slot).
    # Undersize it to overcommit: admission then blocks on pool pressure
    num_pages: int = 0
    prefill_chunk: int = 32  # prompt tokens per prefill program call
    max_queue: int = 256  # bounded admission queue (overflow -> 503)
    max_new_tokens: int = 32  # default token budget per request
    # a consumer this many frames behind its stream is broken, not slow
    stream_outbox_limit: int = 4096
    gauge_period_s: float = 0.5

    @property
    def pages_per_slot(self) -> int:
        return max(1, math.ceil(self.max_seq_len / self.page_size))

    def pool_pages(self) -> int:
        return int(self.num_pages) or self.num_slots * self.pages_per_slot


class BufferSink:
    """Delivery sink for non-streaming callers: collect every token,
    fire done callbacks once, raise typed errors from ``result``."""

    def __init__(self):
        self.tokens: List[int] = []
        self.error: Optional[str] = None
        self.overloaded = False
        self._done = threading.Event()
        self._cbs: List[Any] = []
        self._lock = named_lock("BufferSink._lock")

    def emit(self, frame: dict) -> None:
        """Engine-thread only (single producer)."""
        self.tokens.extend(frame["t"])
        if frame["error"]:
            self.error = str(frame["error"])
        if frame["done"]:
            with self._lock:
                self._done.set()
                cbs, self._cbs = self._cbs, []
            for cb in cbs:
                cb(self)

    def add_done_callback(self, cb) -> None:
        with self._lock:
            if not self._done.is_set():
                self._cbs.append(cb)
                return
        cb(self)

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self._done.wait(timeout):
            raise TimeoutError("engine request did not complete in time")
        if self.error is not None:
            raise EngineStreamError(self.error)
        return list(self.tokens)


class InferenceEngine:
    """Continuous-batching engine over a tp-sharded paged LLM.

    ``llm`` is a ``ShardedLLM`` (serve/llm.py) — its ``engine_programs``
    builds the three jitted programs (pool init, prefill chunk, decode
    step) over the replica's mesh; everything else here is host-side.
    """

    def __init__(self, llm, config: Optional[EngineConfig] = None, deployment: str = "llm"):
        cfg = config or EngineConfig()
        if cfg.max_seq_len > llm.cfg.max_seq_len:
            raise ValueError(
                f"engine max_seq_len {cfg.max_seq_len} exceeds the model's "
                f"{llm.cfg.max_seq_len}"
            )
        self.cfg = cfg
        self.llm = llm
        self.deployment = deployment
        self._programs = llm.engine_programs(
            num_pages=cfg.pool_pages(), page_size=cfg.page_size, num_slots=cfg.num_slots
        )
        self._pages = self._programs["init"]()
        self.cache = PagedKVCache(
            cfg.num_slots, cfg.pages_per_slot, cfg.pool_pages(), cfg.page_size
        )
        self.sched = EngineScheduler(
            self.cache, max_queue=cfg.max_queue, prefill_chunk=cfg.prefill_chunk
        )
        self._lock = named_rlock("InferenceEngine._lock")
        # stream sinks with frames still queued for the wire: the ring is
        # finite, so streams longer than it need flush retries after the
        # consumer drains slots — the loop (and the idle tick) provide them
        self._laggards: set = set()
        # parked defrag requests, executed by the loop at iteration
        # boundaries (see defrag())
        self._defrag_reqs: List = []
        # staged weight hot-swap (update_weights): applied atomically at
        # the next iteration boundary so no prefill/decode program ever
        # sees a half-swapped tree
        self._pending_params = None
        self.weight_updates = 0
        self._wake = threading.Event()
        self._stop = False
        self._fatal: Optional[str] = None
        self._gauges = None
        self._last_tick = 0.0
        # what each member of the pool is, by the model's word
        # (``LlamaModel.pool_roles``): "pages", "counter", "state" or "expert_reads"
        self._pool_roles = tuple(llm.model.pool_roles())
        # an expert model's routing counter (a wrapping int32 per expert on
        # the device): its last reading, and the replica's running totals.
        # None for a model without one.  The same pair for the count of
        # expert weight reads, at a model whose pool carries one
        self._moe_seen = None
        self._moe_load = None
        self._reads_seen = 0
        self._expert_reads = 0 if "expert_reads" in self._pool_roles else None
        # per-slot state beside the pages: its size, and how many chunks
        # began a sequence and so reset their slot's state
        self._state_bytes = sum(int(a.nbytes) for a, role in zip(self._pages, self._pool_roles) if role == "state")
        self._state_bytes_per_slot = self._state_bytes / cfg.num_slots
        self.state_resets = 0
        # what the cache keeps a position, over all layers: the bytes of the
        # pool's "pages" members over the positions they hold (a model's K and
        # V heads, or one latent row)
        paged = sum(int(getattr(a, "nbytes", 0)) for a, role in zip(self._pages, self._pool_roles) if role == "pages")
        self._cache_bytes_per_position = paged / (cfg.pool_pages() * cfg.page_size)
        self._tokens_reported = 0
        self._published = (0.0, 0.0)  # turn_s, sync_wait_s as of the last publish
        self.iterations = 0
        # the thread's own clocks (seconds, this thread their only writer,
        # whole turns only): inside ``_iteration``, of that waiting in the
        # blocking reads for a device still busy, and waiting for ``_lock``.
        # What is left of a turn is the host's work; ``sync_wait_s`` no longer
        # growing means the host sets the pace (DESIGN.md, "Observability")
        self.turn_s = 0.0
        self.sync_wait_s = 0.0
        self.lock_wait_s = 0.0
        self._sync_ns = self._lock_ns = 0  # of the turn under way
        # the token frontier, on the device: the last decode step's result
        # (zeros before the first), which the next step decodes from unread
        self._frontier = self._programs["place"](np.zeros(cfg.num_slots, np.int32))
        self._no_token = self._programs["place"](np.int32(0))  # ``join_token`` of a step nobody joins
        # the decode step in flight: (its result, [(request, slot), ...]),
        # dispatched and not yet read; None between a read and the next dispatch
        self._ahead = None
        self.decode_steps = 0
        # decode steps dispatched while the step before's tokens were unread,
        # and rows computed for a request that had ended by the time they were read
        self.steps_ahead = 0
        self.rows_discarded = 0
        # how far the paged programs' walk over context blocks engages
        # (models/llama.py): blocks walked, and blocks of whole tables, summed
        # over prefill chunks and decode steps from the positions of each call
        from ray_tpu.models.llama import ctx_block_pages

        block_pages = ctx_block_pages(cfg.pages_per_slot, cfg.page_size)
        self._ctx_block = block_pages * cfg.page_size
        self._ctx_blocks_per_call = -(-cfg.pages_per_slot // block_pages)
        self.ctx_blocks_walked = 0
        self.ctx_blocks_full = 0
        # positions held by the rows of each decode step, summed over steps:
        # what a step's attention has to read of the cache
        self.ctx_positions_live = 0
        # the gauges' publisher: every write is a blocking round trip to
        # the head, so none of them is made on the engine thread
        self._publish_lock = named_lock("InferenceEngine._publish_lock")
        self._halt = threading.Event()
        self._publisher = threading.Thread(
            target=self._publish_loop, name=f"gauges-{deployment}", daemon=True
        )
        self._thread = threading.Thread(
            target=self._run, name=f"engine-{deployment}", daemon=True
        )
        self._thread.start()
        self._publisher.start()

    # -------------------------------------------------------------- intake

    def submit(
        self,
        prompt: List[int],
        max_new_tokens: Optional[int] = None,
        eos_token: Optional[int] = None,
        trace: Optional[dict] = None,
        sink=None,
    ) -> EngineRequest:
        """Enqueue one request.  Raises EngineOverloadedError on a full
        queue (the bounded failure mode), ValueError on capacity misuse,
        EngineStreamError after a fatal engine stop."""
        serve_tracing.stamp(trace, "serve_engine_submit")
        with self._lock:
            # stop checked UNDER the lock: a submit racing the loop's
            # fatal teardown must either see _stop here or land in the
            # queue before fail_all drains it — never slip in after and
            # hang its caller on a queue nobody services
            if self._stop:
                raise EngineStreamError(self._fatal or "engine stopped")
            req = self.sched.submit(
                prompt,
                max_new_tokens if max_new_tokens is not None else self.cfg.max_new_tokens,
                eos_token=eos_token,
                trace=trace,
                sink=sink if sink is not None else BufferSink(),
            )
            if trace is not None:
                trace["rid"] = req.rid  # joins the record to the engine's request
        # only an ACCEPTED request defers sealing to the engine — a
        # rejected one (overload/capacity) must still be sealed by the
        # submitting handler's finally, or its record would never ship
        serve_tracing.defer_finish(trace)
        self._wake.set()
        return req

    def cancel(self, req: EngineRequest) -> None:
        """Consumer abandoned the request: retire it at the next
        iteration boundary.  A row of it may be in the step in flight:
        what that computes is discarded at the read."""
        req.cancelled = True
        self._wake.set()

    # ------------------------------------------------------------ the loop

    @graftsan.loop_root
    def _run(self) -> None:
        # the resident loop is its own profiler role: sampled stacks from
        # this thread aggregate under "engine", not the host worker, so
        # `ray-tpu profile` separates decode-step time from actor-call
        # time on the same process (one dict write; no-op when the
        # profiler plane is hard-off)
        from ray_tpu._private import profiler

        profiler.set_thread_role("engine")
        try:
            while not self._stop:
                # read without the lock: a hint only.  Whoever gives the loop
                # work sets ``_wake`` after it, so a stale "no" costs nothing,
                # and a turn looks again under the lock
                busy = self.sched.has_work()
                # a step whose rows all ended while it ran is read (and
                # discarded) by one more turn: nothing is in flight past here
                if not busy and self._ahead is None:
                    self._run_defrags()
                    self._flush_laggards()
                    self._tick()
                    fast = any(
                        getattr(s, "flushable", lambda: False)()
                        for s in self._laggards
                    )
                    with span("engine/idle"):
                        self._wake.wait(0.002 if fast else 0.05)
                    self._wake.clear()
                    continue
                self._iteration()
        except BaseException as e:  # noqa: BLE001 -- a dead loop must fail every caller, typed
            self._fatal = f"engine loop died: {type(e).__name__}: {e}"
            import logging

            logging.getLogger(__name__).exception("inference engine loop died")
        finally:
            self._stop = True
            reason = self._fatal or "engine shut down"
            ahead, self._ahead = self._ahead, None
            if ahead is not None:
                # the device finishes what was queued before the pool goes;
                # its tokens go nowhere: every request fails just below
                try:
                    np.asarray(ahead[0])
                except Exception:  # noqa: BLE001 -- the error that killed the loop, once more
                    pass
            with self._lock:
                victims = self.sched.fail_all(reason)
                parked, self._defrag_reqs = self._defrag_reqs, []
            for req in victims:
                self._deliver(req, [], done=True, error=reason)
            for done, result in parked:  # never strand a defrag waiter
                result.update({"moves": 0, "error": reason})
                done.set()
            self._halt.set()
            self._publish_gauges()  # the last state, once: nothing is left to serve

    def update_weights(self, params=None, *, ref=None) -> None:
        """Stage a live weight hot-swap; applied at the next iteration
        boundary (decode never sees a half-swapped tree; the step in
        flight at that boundary keeps the weights it was dispatched with).

        ``params`` is a pytree matching ``llm.params`` OR a flat 1-D
        vector (``ravel_pytree`` order — what a trainer broadcasts through
        the device object tier).  ``ref`` is an ObjectRef to either form:
        resolving it here means a device-tier ref lands zero-copy when the
        trainer shares this process/mesh, and rides the collective pull
        plane cross-node — the host object path never re-serializes the
        checkpoint (core/DEVICE_TIER.md).

        Either form is placed on the replica's mesh as ``llm.params`` is
        (``ShardedLLM.place``) here, on the caller's thread: the swap
        itself is one assignment, and both programs stay the ones compiled."""
        if (params is None) == (ref is None):
            raise ValueError("update_weights wants exactly one of params=/ref=")
        if ref is not None:
            import ray_tpu

            params = ray_tpu.get(ref, timeout=300)
        if hasattr(params, "ndim") and getattr(params, "ndim") == 1:
            # flat vector → this model's own tree structure
            import jax.numpy as jnp
            from jax.flatten_util import ravel_pytree

            _, unravel = ravel_pytree(self.llm.params)
            params = unravel(jnp.asarray(params))
        new = self.llm.place(params)
        with self._lock:
            self._pending_params = new
        self._wake.set()

    def _apply_pending_params(self) -> None:
        if self._pending_params is None:  # staged under the lock; seen a turn late at worst
            return
        with self._locked():
            new, self._pending_params = self._pending_params, None
        self.llm.params = new
        self.weight_updates += 1

    @contextlib.contextmanager
    def _locked(self):
        """Engine thread, inside a turn: hold ``_lock`` for the body.  The
        ACQUISITION alone is the ``engine/lock`` span and ``lock_wait_s``:
        time the turn's work waited for ``submit()`` or a ``stats()`` caller
        on another thread."""
        t0 = time.perf_counter_ns()
        with span("engine/lock"):
            self._lock.acquire()
        self._lock_ns += time.perf_counter_ns() - t0
        try:
            yield
        finally:
            self._lock.release()

    def _iteration(self) -> None:
        t0 = time.perf_counter_ns()
        self._sync_ns = self._lock_ns = 0  # what an idle turn's tick read is no turn's wait
        with span("engine/iteration"):
            self.iterations += 1
            with span("engine/admit"):
                self._apply_pending_params()
                self._run_defrags()
                with self._locked():
                    reaped = self._reap_cancelled()
                    admitted = self.sched.admit()
                for req in reaped:  # their final frames, with the lock released
                    self._deliver(req, [], done=True, error=None)
                for req in admitted:
                    serve_tracing.stamp(req.trace, "serve_engine_admit")

            # -- one prefill chunk (chunked: decode never waits on a whole prompt)
            with self._locked():
                pf = self.sched.next_prefill()
            joined = None
            if pf is not None:
                with span("engine/prefill"):
                    joined = self._prefill_chunk(*pf)

            # -- one decode step over the whole fleet: ONE program, any mix of
            # sequence lengths, inactive slots masked.  It goes out BEFORE the
            # step before it is read; then the reads, oldest first: the step in
            # flight since last turn, then this turn's chunk (which waits for
            # the chunk alone: the device moves on to the step just queued)
            fleet = self.sched.decode_fleet()
            ahead, self._ahead = self._ahead, None
            if fleet or ahead is not None or joined is not None:
                with span("engine/decode"):
                    if fleet:
                        self._decode_step(fleet, joined)
                        self.steps_ahead += ahead is not None
                    if ahead is not None:
                        self._read_step(*ahead)
                    if joined is not None:
                        self._read_first(*joined)
            self._flush_laggards()
            self._tick()
        # the turn first: a reader that takes the waits first (``stats()``)
        # never sees them exceed it
        self.turn_s += (time.perf_counter_ns() - t0) * 1e-9
        self.sync_wait_s += self._sync_ns * 1e-9
        self.lock_wait_s += self._lock_ns * 1e-9

    def _reap_cancelled(self) -> List[EngineRequest]:
        """Lock held.  Retire cancelled running requests at the iteration
        boundary — and seal their (deferred) trace records: a cancelled
        request still happened.  The caller delivers each its final frame.
        (A row of one in the step in flight is discarded when that step is
        read.)"""
        victims = [r for r in self.running_snapshot() if r.cancelled]
        for req in victims:
            self.sched.retire(req, error=None)
        victims += self.sched.drop_cancelled_queued()
        for req in victims:
            if req.trace is not None:
                req.trace["tokens"] = len(req.out)
            serve_tracing.finish_request(req.trace, error=False, final=True)
        return victims

    def running_snapshot(self) -> List[EngineRequest]:
        return list(self.sched.running.values())

    def _note_walk(self, longest_pos: int) -> None:
        """Count one program call whose longest live position is
        ``longest_pos``: the blocks its attention walks, as the program
        itself bounds them, and the blocks of a whole table."""
        self.ctx_blocks_walked += min(
            longest_pos // self._ctx_block + 1, self._ctx_blocks_per_call
        )
        self.ctx_blocks_full += self._ctx_blocks_per_call

    def _prefill_chunk(self, req: EngineRequest, start: int, toks: List[int]):
        """Dispatch one chunk.  Returns ``(req, first)`` when the prompt is
        now fully resident: ``first``, the chunk's sampled token, is the
        request's first generated token, still on the device."""
        with span("engine/build"):
            if start == 0:
                serve_tracing.stamp(req.trace, "serve_prefill_start")
                self.state_resets += 1
            C = self.cfg.prefill_chunk
            n_valid = len(toks)
            chunk = np.zeros(C, np.int32)
            chunk[:n_valid] = toks
            # a copy, here and in the decode step: the call may still be
            # reading its arguments when a later retirement rewrites the table
            table = self.cache.tables[req.slot].copy()
            self._note_walk(start + n_valid - 1)
        with span("engine/dispatch"):
            first, self._pages = self._programs["prefill"](
                self.llm.params,
                self._pages,
                table,
                chunk,
                np.int32(start),
                np.int32(n_valid),
                np.int32(req.slot),
            )
        if not self.sched.note_prefill(req, n_valid):
            return None
        req.state = DECODE
        req.unread = 1
        return req, first

    def _read_first(self, req: EngineRequest, first) -> None:
        """The first token of a prompt whose last chunk this turn ran:
        host-visible right here — the TTFT endpoint."""
        tok0 = int(self._await(first))
        with span("engine/deliver"):
            serve_tracing.stamp(req.trace, "serve_first_token")
            self._hand_on([(req, tok0)])

    def _await(self, result) -> np.ndarray:
        """The blocking device->host read, wherever this thread makes one:
        the ``engine/sync`` span and, inside a turn, ``sync_wait_s``.  Under
        it the host waits for a device that is busy."""
        t0 = time.perf_counter_ns()
        with span("engine/sync"):
            out = np.asarray(result)
        self._sync_ns += time.perf_counter_ns() - t0
        return out

    def _read_member(self, role: str) -> Optional[np.ndarray]:
        """The newest pool's member called ``role`` (``pool_roles``), read to
        the host, or None at a model whose pool has none."""
        if role not in self._pool_roles:
            return None
        return self._await(self._pages[self._pool_roles.index(role)])

    def _decode_step(self, fleet: List[EngineRequest], joined) -> None:
        """Dispatch one decode step from the frontier on the device.  Every
        row of ``fleet`` either ran in the step before (its input is that
        step's result, at its slot) or is ``joined``'s request."""
        with span("engine/build"):
            S = self.cfg.num_slots
            slots = [req.slot for req in fleet]
            positions = np.zeros(S, np.int32)
            positions[slots] = [r.prompt_len + len(r.out) + r.unread - 1 for r in fleet]
            active = np.zeros(S, bool)
            active[slots] = True
            for req in fleet:
                req.unread += 1
            join_slot, join_token = -1, self._no_token
            if joined is not None and active[joined[0].slot]:  # a budget of one token never joins
                join_slot, join_token = joined[0].slot, joined[1]
            tables = self.cache.tables.copy()
            self._note_walk(int(positions.max()))
            self.ctx_positions_live += int(positions.sum()) + len(fleet)  # a row at position p holds p + 1
        with span("engine/dispatch"):
            nxt, self._pages = self._programs["decode"](
                self.llm.params,
                self._pages,
                tables,
                self._frontier,
                positions,
                active,
                np.int32(join_slot),
                join_token,
            )
        self._frontier = nxt
        # each row with the slot it ran in: a retirement unsets ``req.slot``
        self._ahead = (nxt, list(zip(fleet, slots)))
        self.decode_steps += 1

    def _read_step(self, nxt, rows) -> None:
        """Read one decode step's tokens (the host sees them one step after
        the device had them) and hand them on.  A device error surfaces
        here, a step late."""
        nxt = self._await(nxt)
        with span("engine/deliver"):
            toks = nxt.tolist()
            # a request that ended (EOS, cancel) after this step went out has
            # given its slot up: its row is never delivered nor counted.  What
            # the row wrote is out of every live request's reach (DESIGN.md,
            # "One step in flight")
            live = [(req, toks[slot]) for req, slot in rows if req.slot == slot]
            self.rows_discarded += len(rows) - len(live)
            self._hand_on(live)

    def _hand_on(self, fresh: List[Tuple[EngineRequest, int]]) -> None:
        """``fresh`` is ``[(request, token just read), ...]``: note every
        token and retire what ended under ONE hold of the lock, then emit a
        frame a request with the lock released — a sink may block (a full
        ring, a slow consumer) and ``stats()`` on another thread must not
        wait for that.  Inside the caller's ``engine/deliver``: the lock's
        acquisition and the pass over the sinks have spans of their own, so
        a long delivery says which of the three it was."""
        with self._locked():
            ended = self.sched.note_tokens(fresh)
            for (req, _), end in zip(fresh, ended):
                req.unread -= 1
                if end:
                    self.sched.retire(req)
        with span("engine/emit"):
            for (req, tok), end in zip(fresh, ended):
                if end:
                    serve_tracing.stamp(req.trace, "serve_decode_end")
                    if req.trace is not None:
                        req.trace["tokens"] = len(req.out)
                    serve_tracing.finish_request(req.trace, error=False, final=True)
                self._deliver(req, [tok], done=end)

    # ----------------------------------------------------------- delivery

    def _deliver(
        self,
        req: EngineRequest,
        toks: List[int],
        done: bool = False,
        error: Optional[str] = None,
    ) -> None:
        if error is not None:
            serve_tracing.stamp(req.trace, "serve_decode_end")
            serve_tracing.finish_request(req.trace, error=True, final=True)
        sink = req.sink
        if sink is None:
            return
        try:
            sink.emit({"t": toks, "done": done, "error": error})
            if getattr(sink, "needs_flush", None) is not None and sink.needs_flush():
                self._laggards.add(sink)
        except Exception:  # noqa: BLE001 -- a broken consumer must not stall the fleet
            req.sink = None

    def _flush_laggards(self) -> None:
        """Re-flush streams whose channel ring was full at emit time —
        the consumer drains slots at its own pace, so delivery of a
        sequence longer than the ring depth completes here."""
        if not self._laggards:
            return
        with span("engine/flush"):
            for sink in list(self._laggards):
                try:
                    sink.flush()
                    if not sink.needs_flush():
                        self._laggards.discard(sink)
                except Exception:  # noqa: BLE001 -- broken stream: its consumer sees the typed error
                    self._laggards.discard(sink)

    # -------------------------------------------------------------- defrag

    def defrag(self, timeout: float = 30.0) -> Dict[str, Any]:
        """Compact the page pool: move allocated pages to the lowest
        physical ids and rewrite the page tables.  The device copy is
        dispatched ON THE ENGINE THREAD at an iteration boundary — the loop
        runs jitted steps outside the lock with the pool buffers DONATED,
        so any other thread touching ``self._pages`` races a buffer that may
        already be consumed; this call just parks a request and waits."""
        done = threading.Event()
        result: Dict[str, Any] = {}
        with self._lock:
            if self._stop:
                raise EngineStreamError(self._fatal or "engine stopped")
            self._defrag_reqs.append((done, result))
        self._wake.set()
        if not done.wait(timeout):
            raise TimeoutError("defrag did not run within the timeout")
        return result

    def _run_defrags(self) -> None:
        """Engine thread, iteration boundary: the one place where no call
        is being made with the donated pool.  A decode step may still be in
        flight: ``self._pages`` is then its result, the moves below queue
        behind it on the device, and it reads the copy of the tables it was
        given; every later call is built from the rewritten ones."""
        if not self._defrag_reqs:  # parked under the lock by a caller that then sets ``_wake``
            return
        with self._lock:
            reqs, self._defrag_reqs = self._defrag_reqs, []
            moves = self.cache.compaction_plan()
            if moves:
                # one gather/scatter per buffer: every source page
                # materializes before any write, so overlapping src/dst
                # ranges are safe
                srcs = np.asarray([m[0] for m in moves], np.int32)
                dsts = np.asarray([m[1] for m in moves], np.int32)
                # the members indexed by page move; a counter and per-slot state are handed on
                self._pages = tuple(
                    a.at[:, dsts].set(a[:, srcs]) if role == "pages" else a
                    for a, role in zip(self._pages, self._pool_roles)
                )
                self.cache.apply_compaction(moves)
            free = self.cache.free_pages()
        frag = fragmentation_of(free)
        for done, result in reqs:
            result.update({"moves": len(moves), "fragmentation": frag})
            done.set()

    # ------------------------------------------------------------- observe

    def stats(self) -> Dict[str, Any]:
        """Any thread.  The engine's lock is held for the counts and a copy
        of the free list; the walk over that copy is made with it released
        (``_hand_on`` on the engine thread waits for this lock)."""
        with self._lock:
            out = self.sched.stats()
            out.update(self.cache.stats())
            free = self.cache.free_pages()
        out["fragmentation"] = fragmentation_of(free)
        out["iterations"] = float(self.iterations)
        # the waits before the turn that holds them (``_iteration`` writes the
        # other way round): turn_s >= sync_wait_s + lock_wait_s in every reply
        out["lock_wait_s"] = self.lock_wait_s
        out["sync_wait_s"] = self.sync_wait_s
        out["turn_s"] = self.turn_s
        out["decode_steps"] = float(self.decode_steps)
        out["steps_ahead"] = float(self.steps_ahead)
        out["rows_discarded"] = float(self.rows_discarded)
        out["ctx_blocks_walked"] = float(self.ctx_blocks_walked)
        out["ctx_blocks_full"] = float(self.ctx_blocks_full)
        out["ctx_positions_live"] = float(self.ctx_positions_live)
        out["cache_bytes_per_position"] = float(self._cache_bytes_per_position)
        out.update({f"compile_{k}": v for k, v in self.compile_stats().items()})
        load = self._moe_load
        if load is not None:  # as of the last gauge tick (gauge_period_s)
            # the counter is over the router's experts; a replica that holds
            # a share of them serves the assignments that fell on its own
            held = load[self.llm.model.held_experts()]
            out["moe_assignments"] = out["moe_assignments_held"] = float(held.sum())
            out["moe_assignments_seen"] = float(load.sum())
            out["moe_expert_load"] = held.tolist()
        if self._expert_reads is not None:  # as of the same tick
            out["moe_expert_reads"] = float(self._expert_reads)
        if self._state_bytes:
            out["state_bytes"] = float(self._state_bytes)
            out["state_bytes_per_slot"] = float(self._state_bytes_per_slot)
            out["state_resets"] = float(self.state_resets)
        return out

    def compile_stats(self) -> Dict[str, int]:
        """Compiled-program cache sizes — the no-recompilation assertion
        surface: after warmup each stays at 1 no matter the length mix."""
        out = {}
        for name in ("prefill", "decode"):
            fn = self._programs[name]
            try:
                out[name] = int(fn._cache_size())
            except Exception:  # noqa: BLE001 -- private jit API; absence degrades the stat only
                out[name] = -1
        return out

    def _tick(self) -> None:
        """Engine thread, at most every ``gauge_period_s``: what of the
        gauges has to happen between two calls of the programs.  That is
        the routing counter's read at a model that has one (between turns
        no call is being made with the donated pool, so the member read is
        the newest result; the read waits for the step in flight) and the
        fold of it into the running totals, which is all that
        ``engine/gauges`` still holds here: the gauges themselves are the
        publisher thread's."""
        now = time.monotonic()
        if now - self._last_tick < self.cfg.gauge_period_s:
            return
        self._last_tick = now
        seen = reads = None
        try:
            seen, reads = self._read_member("counter"), self._read_member("expert_reads")  # one step's result: the second read waits for nothing
        except Exception:  # noqa: BLE001 -- a dead loop's pool may be gone; the totals stay as they were
            pass
        with span("engine/gauges"):
            if reads is not None:  # a wrapping int32 too
                self._expert_reads += (int(reads) - self._reads_seen) % 2**32
                self._reads_seen = int(reads)
            if seen is not None:
                seen = seen.astype(np.uint32)
                # the device counts in wrapping int32: the difference between
                # two readings is exact as long as fewer than 2**32
                # assignments go to one expert between ticks
                if self._moe_load is None:  # the pool starts at zero
                    self._moe_seen, self._moe_load = np.zeros_like(seen), np.zeros(seen.shape, np.int64)
                # a new array each tick: stats() on another thread keeps a whole one
                self._moe_load = self._moe_load + (seen - self._moe_seen).astype(np.int64)
                self._moe_seen = seen

    def _publish_loop(self) -> None:
        """The publisher thread: the gauges every ``gauge_period_s`` until
        the loop ends.  It writes no ``engine/*`` span: those are the engine
        thread's turn, and their readers take every thread's."""
        while not self._halt.wait(self.cfg.gauge_period_s):
            self._publish_gauges()

    def _publish_gauges(self) -> None:
        """Publish slot/page occupancy, the token counter and the host's
        share of the engine thread's turn.  Outside a connected worker (unit
        tests drive the engine bare) this is a no-op."""
        try:
            from ray_tpu._private import worker as worker_mod

            worker_mod._require_connected()
        except Exception:  # noqa: BLE001 -- bare engine: no metrics plane to publish to
            return
        try:
            with self._publish_lock:  # the publisher's tick and the loop's last publish
                g, c = self._ensure_gauges()
                st = self.stats()
                dep = {"deployment": self.deployment}
                g["slots"].set(st["slots_active"], {**dep, "kind": "active"})
                g["slots"].set(st["slots_decode"], {**dep, "kind": "decode"})
                g["slots"].set(st["slots_prefill"], {**dep, "kind": "prefill"})
                g["slots"].set(st["slots_total"], {**dep, "kind": "total"})
                g["queue"].set(st["queue_depth"], dep)
                g["pages"].set(st["pages_used"], {**dep, "kind": "used"})
                g["pages"].set(st["pages_total"], {**dep, "kind": "total"})
                g["frag"].set(st["fragmentation"], dep)
                # of the turns since the last publish, the share the thread did
                # not spend waiting for the device; 0 while no turn ran
                turn, wait = st["turn_s"], st["sync_wait_s"]
                d_turn, d_wait = turn - self._published[0], wait - self._published[1]
                self._published = (turn, wait)
                # (two replies may split a turn between them: never below 0)
                g["host"].set(max(0.0, 1.0 - d_wait / d_turn) if d_turn > 0 else 0.0, dep)
                delta = int(st["tokens_generated"]) - self._tokens_reported
                if delta > 0:
                    c.inc(delta, dep)
                    self._tokens_reported += delta
        except Exception:  # noqa: BLE001 -- observability is best-effort; serving already progressed
            import logging

            logging.getLogger(__name__).debug(
                "engine gauge publish failed", exc_info=True
            )

    def _ensure_gauges(self):
        if self._gauges is None:
            from ray_tpu.util.metrics import Counter, Gauge

            self._gauges = (
                {
                    "slots": Gauge(
                        "ray_tpu_serve_engine_slots",
                        "Engine slot occupancy by kind (active/prefill/decode/total)",
                        tag_keys=("deployment", "kind"),
                    ),
                    "queue": Gauge(
                        "ray_tpu_serve_engine_queue_depth",
                        "Requests waiting in the engine's bounded admission queue",
                        tag_keys=("deployment",),
                    ),
                    "pages": Gauge(
                        "ray_tpu_serve_engine_kv_pages",
                        "Paged KV cache pool occupancy (used/total pages)",
                        tag_keys=("deployment", "kind"),
                    ),
                    "frag": Gauge(
                        "ray_tpu_serve_engine_page_fragmentation",
                        "Free-list fragmentation of the KV page pool (0=contiguous)",
                        tag_keys=("deployment",),
                    ),
                    "cache": Gauge(
                        "ray_tpu_serve_engine_cache_bytes_per_position",
                        "Bytes the page pool keeps a cached position, all layers (K and V heads, or one latent row)",
                        tag_keys=("deployment",),
                    ),
                    "state": Gauge(
                        "ray_tpu_serve_engine_state_bytes_per_slot",
                        "Bytes of per-slot state the pool keeps a slot beside the pages (recurrent state, conv windows)",
                        tag_keys=("deployment",),
                    ),
                    "host": Gauge(
                        "ray_tpu_serve_engine_host_share",
                        "Share of the engine thread's turns not spent waiting for the device (1=host-bound)",
                        tag_keys=("deployment",),
                    ),
                },
                Counter(
                    "ray_tpu_serve_engine_tokens_total",
                    "Tokens generated by the continuous-batching engine",
                    tag_keys=("deployment",),
                ),
            )
            # fixed when the pool was made: written once, not a round trip a period
            self._gauges[0]["cache"].set(self._cache_bytes_per_position, {"deployment": self.deployment})
            if self._state_bytes:  # a model with per-slot state only: absent elsewhere
                self._gauges[0]["state"].set(self._state_bytes_per_slot, {"deployment": self.deployment})
        return self._gauges

    # ------------------------------------------------------------ teardown

    def reconfigure(self, max_queue: Optional[int] = None) -> None:
        """Live-adjustable knobs only (everything geometric is baked into
        compiled programs)."""
        if max_queue is not None:
            self.sched.max_queue = int(max_queue)

    def shutdown(self, timeout: float = 10.0) -> None:
        self._stop = True
        self._wake.set()
        self._thread.join(timeout)
        self._halt.set()  # the loop's end sets it too; a loop that outlived the join has not
        self._publisher.join(timeout)
