"""Training input: a host thread that makes packed documents from the seed
and keeps a few batches ready.  One general generator; a traffic mix is its
data file.

Documents have log-normal lengths and are separated by an end-of-text token,
then packed into rows of ``seq + 1`` tokens.  A token follows the previous one
through a fixed seeded permutation with probability ``bigram_share`` and is
else drawn from a Zipf unigram, so there is structure a model can learn and
the loss can fall on batches it has never seen."""

from __future__ import annotations

import math
import queue
import threading
from typing import Mapping

import numpy as np


class PackedDocuments:
    def __init__(self, traffic: Mapping, seed: int, batch: int, vocab: int):
        self.seq = int(traffic["seq"])
        self.batch = batch
        self.vocab = vocab
        self.eos = vocab - 1
        self.doc = traffic["doc_len"]
        tok = traffic["tokens"]
        self.bigram_share = float(tok["bigram_share"])
        self.rng = np.random.default_rng(seed)
        # the language is fixed (seed-independent): every run learns the same one
        lang = np.random.default_rng(20260927)
        ranks = np.arange(1, vocab, dtype=np.float64)
        pmf = ranks ** -float(tok["zipf_a"])
        self.cdf = np.cumsum(pmf / pmf.sum())
        self.rank_to_token = lang.permutation(vocab - 1)
        self.follows = lang.permutation(vocab - 1)

    def _stream(self, n: int) -> np.ndarray:
        """n tokens of the language, no document boundaries yet."""
        rng = self.rng
        draws = self.rank_to_token[np.searchsorted(self.cdf, rng.random(n)).clip(0, self.vocab - 2)]
        follow = rng.random(n) < self.bigram_share
        follow[0] = False
        # depth of each position in its run of follow-ons
        idx = np.arange(n)
        last_free = np.maximum.accumulate(np.where(~follow, idx, 0))
        depth = idx - last_free
        out = draws.copy()
        for d in range(1, int(depth.max()) + 1):
            at = np.flatnonzero(depth == d)
            out[at] = self.follows[out[at - 1]]
        return out

    def next_batch(self):
        """(tokens, targets) int32 [batch, seq]: packed rows, shifted by one."""
        n = self.batch * (self.seq + 1)
        toks = self._stream(n)
        doc = self.doc
        mean_len = max(doc["min"], doc["median"])
        k = int(n / mean_len) + 8
        lens = np.exp(math.log(doc["median"]) + doc["sigma"] * self.rng.standard_normal(k))
        lens = np.clip(lens, doc["min"], doc["max"]).astype(np.int64)
        ends = np.cumsum(lens + 1) - 1
        toks[ends[ends < n]] = self.eos
        rows = toks.reshape(self.batch, self.seq + 1).astype(np.int32)
        return np.ascontiguousarray(rows[:, :-1]), np.ascontiguousarray(rows[:, 1:])


class Prefetcher:
    """``depth`` batches ready ahead of the consumer, made on one thread."""

    def __init__(self, source: PackedDocuments, depth: int):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._source = source
        self._thread = threading.Thread(target=self._fill, daemon=True, name="bench-input")
        self._thread.start()

    def _fill(self):
        while not self._stop.is_set():
            item = self._source.next_batch()
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def __next__(self):
        return self._q.get()

    def __iter__(self):
        return self

    def close(self):
        self._stop.set()
        self._thread.join(5)


def make(traffic: Mapping, seed: int, batch: int, vocab: int) -> Prefetcher:
    return Prefetcher(PackedDocuments(traffic, seed, batch, vocab), int(traffic.get("prefetch", 2)))
