"""Sending one request and recording what the client saw: shared by the
open-loop and the closed-loop generator."""

from __future__ import annotations

import time


def new_record(i: int, prompt_len: int, budget: int, due=None) -> dict:
    return {"i": i, "due": due, "prompt_len": prompt_len, "budget": budget,
            "sent": None, "frames": [], "tokens": 0, "done": None, "error": None}


def send(client, request: dict, rec: dict, t0: float, stream: bool) -> None:
    """Fill ``rec`` with the send time, each frame's arrival and token count,
    and the completion time, all in seconds from ``t0``.  A failed request is
    counted (``rec["error"]``), not fatal."""
    rec["sent"] = time.perf_counter() - t0
    try:
        if stream:
            for frame in client.stream(request["prompt"], request["budget"]):
                rec["frames"].append((time.perf_counter() - t0, len(frame)))
                rec["tokens"] += len(frame)
        else:
            rec["tokens"] = len(client.call(request["prompt"], request["budget"]))
            rec["frames"].append((time.perf_counter() - t0, rec["tokens"]))
        rec["done"] = time.perf_counter() - t0
    except Exception as e:  # noqa: BLE001 -- see the docstring
        rec["error"] = f"{type(e).__name__}: {e}"
