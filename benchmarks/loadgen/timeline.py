"""Calls made at fixed offsets from the start of the measured window (counter
snapshots, the canary request, the profiler), each in its own thread so that
none delays the load."""

from __future__ import annotations

import threading
import time
from typing import Callable, List, Sequence, Tuple


class Timeline:
    def __init__(self, events: Sequence[Tuple[float, Callable[[], None]]]):
        self._events = sorted(events, key=lambda e: e[0])
        self._threads: List[threading.Thread] = []
        self.errors: List[str] = []

    def _guard(self, fn):
        try:
            fn()
        except Exception as e:  # noqa: BLE001 -- reported by the driver as a failed run
            self.errors.append(f"{getattr(fn, '__name__', fn)}: {type(e).__name__}: {e}")

    def start(self, t0: float) -> None:
        """``t0`` is the window's start on ``time.perf_counter``."""

        def pace():
            for off, fn in self._events:
                delay = t0 + off - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                th = threading.Thread(target=self._guard, args=(fn,), daemon=True)
                th.start()
                self._threads.append(th)

        self._pacer = threading.Thread(target=pace, daemon=True)
        self._pacer.start()

    def join(self, timeout: float) -> bool:
        end = time.perf_counter() + timeout
        self._pacer.join(max(0.0, end - time.perf_counter()))
        for th in list(self._threads):
            th.join(max(0.0, end - time.perf_counter()))
        return not self._pacer.is_alive() and not any(t.is_alive() for t in self._threads)
