"""Closed loop: a fixed number of clients, each sending its next request when
the last one returned.  One general generator; a traffic mix is its data file."""

from __future__ import annotations

import random
import threading
import time
from typing import List, Mapping

from benchmarks.loadgen import client as client_mod
from benchmarks.loadgen import dists
from benchmarks.loadgen.timeline import Timeline

def request(traffic: Mapping, seed: int, i: int, vocab: int) -> dict:
    """The i-th request of a run.  Lengths come in blocks of ``length_block``
    requests: each block holds that many quantiles of each distribution,
    permuted by (seed, block).  Small blocks keep the tokens offered up to any
    instant nearly equal across seeds (with blocks of 64 the tokens completed
    in a window spread by 3% between seeds and by nothing between two runs of
    one seed: my chip runs, PR 23), while every seed still sends another order."""
    size = int(traffic.get("length_block", 64))
    block, k = divmod(i, size)
    rng = random.Random(seed * 1_000_003 + block)
    plens = dists.stratified_ints(traffic["prompt_len"], size, rng)
    olens = dists.stratified_ints(traffic["output_len"], size, rng)
    prng = random.Random((seed * 1_000_003 + block) * 131 + k)
    return {"i": i, "prompt": dists.prompt_tokens(plens[k], vocab, prng), "budget": olens[k]}


def run(client, traffic: Mapping, seed: int, seconds: float, vocab: int, events=()) -> dict:
    stream = bool(traffic.get("stream", False))
    pre = float(traffic.get("preroll_s", 0.0))
    t0 = time.perf_counter() + pre + 0.05
    t_end = t0 + seconds
    records: List[dict] = []
    lock = threading.Lock()
    counter = [0]

    def client_loop():
        while True:
            with lock:
                if time.perf_counter() >= t_end:
                    return
                i = counter[0]
                counter[0] += 1
            r = request(traffic, seed, i, vocab)
            rec = client_mod.new_record(i, len(r["prompt"]), r["budget"])
            with lock:
                records.append(rec)
            client_mod.send(client, r, rec, t0, stream)

    timeline = Timeline(events)
    timeline.start(t0)
    threads = [threading.Thread(target=client_loop, daemon=True, name=f"client-{c}") for c in range(int(traffic["clients"]))]
    for th in threads:
        th.start()
    deadline = t_end + float(traffic.get("drain_s", 30.0))
    for th in threads:
        th.join(max(0.0, deadline - time.perf_counter()))
    timeline.join(max(1.0, deadline - time.perf_counter()))
    records.sort(key=lambda r: r["i"])
    return {"t0": t0, "seconds": seconds, "records": records, "timeline_errors": timeline.errors}
