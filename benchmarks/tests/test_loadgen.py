import json
import os

import numpy as np
import pytest

from benchmarks.loadgen import closed_loop, dists, open_loop, train_job

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _traffic(name):
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def test_open_loop_schedule_repeats_for_a_seed_and_keeps_its_sizes_across_seeds():
    t = _traffic("chat")
    a = open_loop.schedule(t, 3_000_000_001, 45.0, 32768)
    b = open_loop.schedule(t, 3_000_000_001, 45.0, 32768)
    c = open_loop.schedule(t, 17, 45.0, 32768)
    assert a == b
    # the seed changes the prompts' tokens; arrivals and sizes are the mix's one fixed sequence
    assert a[0]["prompt"] != c[0]["prompt"]
    assert [(r["due"], len(r["prompt"]), r["budget"]) for r in a] == [(r["due"], len(r["prompt"]), r["budget"]) for r in c]
    assert sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in c)
    assert sorted(r["budget"] for r in a) == sorted(r["budget"] for r in c)
    assert len(a) == round(t["rate_rps"] * (45.0 + t["preroll_s"]))
    due = [r["due"] for r in a]
    assert due == sorted(due) and due[0] >= -t["preroll_s"] and due[-1] < 45.0
    lens = [len(r["prompt"]) for r in a]
    assert min(lens) >= t["prompt_len"]["min"] and max(lens) <= t["prompt_len"]["max"]
    assert abs(np.median(lens) - t["prompt_len"]["median"]) < 0.1 * t["prompt_len"]["median"]
    assert all(1 <= tok < 32768 for r in a[:5] for tok in r["prompt"])


class _FakeClient:
    """Answers after a fixed service time: 20 ms to the first frame, 5 ms a token."""

    def stream(self, prompt, budget):
        import time

        time.sleep(0.02)
        yield [1]
        for _ in range(budget - 1):
            time.sleep(0.005)
            yield [1]

    def call(self, prompt, budget):
        return [t for f in self.stream(prompt, budget) for t in f]


def test_open_loop_accounts_from_the_due_time_and_reports_lag():
    t = {**_traffic("chat"), "rate_rps": 20.0, "preroll_s": 0.5, "drain_s": 5.0,
         "prompt_len": {"dist": "fixed", "value": 8}, "output_len": {"dist": "fixed", "value": 4}}
    fired = []
    res = open_loop.run(_FakeClient(), t, 5, 2.0, 100, events=[(1.0, lambda: fired.append(1))])
    recs = res["records"]
    assert fired == [1] and not res["timeline_errors"]
    assert len(recs) == 50 and all(r["error"] is None and r["tokens"] == 4 for r in recs)
    for r in recs:
        assert r["sent"] >= r["due"] - 1e-3  # never early
        ttft = r["frames"][0][0] - r["due"]  # from the due time, so lag is inside it
        assert ttft >= 0.02 and ttft < 0.2
        assert len(r["frames"]) == 4
    assert sum(1 for r in recs if r["due"] < 0) > 0  # the pre-roll is scheduled, and not measured


def test_closed_loop_requests_come_from_the_seed_in_blocks_of_equal_sizes():
    t = _traffic("docs")
    a = [closed_loop.request(t, 9, i, 32768) for i in range(128)]
    assert a[5] == closed_loop.request(t, 9, 5, 32768)
    t = {**t, "length_block": 64}
    a = [closed_loop.request(t, 9, i, 32768) for i in range(128)]
    first, second = a[:64], a[64:]
    assert sorted(len(r["prompt"]) for r in first) == sorted(len(r["prompt"]) for r in second)
    assert min(len(r["prompt"]) for r in a) >= 1024 and max(len(r["prompt"]) + r["budget"] for r in a) <= 2048
    res = closed_loop.run(_FakeClient(), {**t, "clients": 4, "preroll_s": 0.2, "drain_s": 5.0,
                                          "prompt_len": {"dist": "fixed", "value": 8}, "output_len": {"dist": "fixed", "value": 4}}, 3, 1.0, 100)
    done = [r for r in res["records"] if r["done"] is not None]
    assert len(done) == len(res["records"]) > 8
    assert max(r["sent"] for r in done) < 1.0  # nothing is sent after the window


def test_packed_documents_shapes_determinism_and_structure():
    t = _traffic("pretrain-1k")
    a = train_job.PackedDocuments(t, 7, 4, 50257).next_batch()
    b = train_job.PackedDocuments(t, 7, 4, 50257).next_batch()
    c = train_job.PackedDocuments(t, 8, 4, 50257).next_batch()
    assert a[0].shape == a[1].shape == (4, 1024) and a[0].dtype == np.int32
    assert (a[0] == b[0]).all() and not (a[0] == c[0]).all()
    assert (a[0][:, 1:] == a[1][:, :-1]).all()  # targets are the tokens shifted by one
    assert 0 <= a[0].min() and a[0].max() < 50257
    eos = (a[0] == 50256).sum()
    assert 2 <= eos <= 40  # about 4096 / 400 documents end in the batch
    src = train_job.PackedDocuments(t, 7, 4, 50257)
    toks = src._stream(20000)
    follows = (toks[1:] == src.follows[toks[:-1]]).mean()
    assert 0.45 < follows < 0.6  # the bigram a model can learn
    pf = train_job.make(t, 7, 2, 50257)
    try:
        assert next(pf)[0].shape == (2, 1024)
    finally:
        pf.close()


def test_quantiles_respect_the_clip():
    spec = {"dist": "lognormal", "median": 100, "sigma": 2.0, "min": 10, "max": 500}
    assert dists.quantile(spec, 0.001) == 10 and dists.quantile(spec, 0.999) == 500
    with pytest.raises(ValueError):
        dists.quantile({"dist": "zipf"}, 0.5)
