"""Request batching: coalesce concurrent calls into one model invocation.

Analog of the reference's @serve.batch (reference: python/ray/serve/
batching.py:46 _BatchQueue, :87 wait_for_batch, :131 decorator).  The
TPU angle: a jitted model wants fixed large batches — callers trickle in
single requests, the queue release them as one padded tensor batch.
"""

from __future__ import annotations

import asyncio
import functools
from typing import Any, Callable, List, Optional


class _BatchQueue:
    def __init__(
        self,
        fn,
        max_batch_size: int,
        batch_wait_timeout_s: float,
        max_pending: Optional[int] = None,
    ):
        self.fn = fn
        self.max_batch_size = max_batch_size
        self.timeout = batch_wait_timeout_s
        self.max_pending = max_pending
        self.queue: List = []  # [(item, future)]
        self._flusher: Optional[asyncio.Task] = None

    def depth(self) -> int:
        return len(self.queue)

    async def submit(self, instance, item):
        from ray_tpu.serve import tracing as serve_tracing

        if self.max_pending is not None and len(self.queue) >= self.max_pending:
            # bounded failure mode: reject at submit (the proxy's 503)
            # instead of queueing unboundedly
            from ray_tpu.exceptions import EngineOverloadedError

            raise EngineOverloadedError(
                f"batch queue full ({self.max_pending} waiting)",
                retry_after_s=max(self.timeout, 0.05) * 4,
            )
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        # capture the submitting request's trace record NOW (submit runs
        # on the request's own context); the flusher task stamps it later
        trace = serve_tracing.current_request()
        serve_tracing.stamp(trace, "serve_queue_enter")
        self.queue.append((item, fut, trace))
        if len(self.queue) >= self.max_batch_size:
            await self._flush(instance)
        elif self._flusher is None or self._flusher.done():
            self._flusher = loop.create_task(self._timed_flush(instance))
        return await fut

    async def _timed_flush(self, instance):
        await asyncio.sleep(self.timeout)
        await self._flush(instance)

    async def _flush(self, instance):
        from ray_tpu.serve import tracing as serve_tracing

        if not self.queue:
            return
        batch, self.queue = self.queue, []
        items = [b[0] for b in batch]
        futs = [b[1] for b in batch]
        traces = [b[2] for b in batch if b[2] is not None]
        for tr in traces:
            serve_tracing.stamp(tr, "serve_queue_exit")
        try:
            if instance is not None:
                results = self.fn(instance, items)
            else:
                results = self.fn(items)
            if asyncio.iscoroutine(results):
                results = await results
            if len(results) != len(items):
                raise ValueError(
                    f"batched fn returned {len(results)} results for {len(items)} inputs"
                )
            for fut, res in zip(futs, results):
                if not fut.done():
                    fut.set_result(res)
        except BaseException as e:  # noqa: BLE001
            for fut in futs:
                if not fut.done():
                    fut.set_exception(e)


def batch(
    _fn=None,
    *,
    max_batch_size: int = 8,
    batch_wait_timeout_s: float = 0.01,
    max_pending: Optional[int] = None,
):
    """Decorator: async method taking a single item → coalesced list calls.

    The wrapped function must accept a LIST of items and return a LIST of
    results (reference semantics).  ``max_pending`` bounds the waiting
    queue: overflow raises EngineOverloadedError at submit (the HTTP
    proxy maps it to 503 + Retry-After); None keeps the legacy unbounded
    behavior."""

    def deco(fn):
        queue = _BatchQueue(fn, max_batch_size, batch_wait_timeout_s, max_pending)

        @functools.wraps(fn)
        async def wrapper(self_or_item, *args):
            # method form: wrapper(self, item); function form: wrapper(item)
            if args:
                return await queue.submit(self_or_item, args[0])
            return await queue.submit(None, self_or_item)

        wrapper._batch_queue = queue
        return wrapper

    if _fn is not None:
        return deco(_fn)
    return deco
