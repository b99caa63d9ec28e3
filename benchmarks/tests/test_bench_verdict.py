"""``correct: false`` and a non-zero exit are statements about the program under
test: nothing the harness does to itself may produce either.  The rules that
hold the harness to that, each on the CPU with no cluster: which probe of a
serving window judges the run, what the two compiled forms of an expert
model's programs may differ in, the wait for the chips, the one retry before
the window, and the collectives' reader old against new."""

import os
import random
import time

import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks import trace_reduce as tr
from benchmarks.drivers import cluster, serve, serve_moe
from benchmarks.loadgen.timeline import Timeline

ALONE = list(range(serve.CANARY_BUDGET))


class FakeClient:
    """A replica that answers every probe, but for those named in ``silent``
    (no answer inside the probe's timeout) and a canary that ``says`` other
    tokens under load."""

    def __init__(self, silent=(), says=None):
        self.silent, self.says, self.now = set(silent), says or {}, None

    def _answer(self, value):
        if self.now in self.silent:
            raise TimeoutError(f"{self.now}: no reply in 90 s")
        return value

    def method(self, name, *args, timeout=None):
        return self._answer({"slots_active": 7, "iterations": 1, "tokens_generated": 1} if name == "engine_stats" else True)

    def stream(self, prompt, budget):
        return self._answer([self.says.get(self.now, ALONE)])

    def call(self, prompt, budget):
        return self._answer(self.says.get(self.now, ALONE))


def probe_window(client):
    """Every probe of a traced window once, each guarded as the generators'
    timeline guards it -> (problems, notes)."""
    probes = serve.Probes(client, [1, 2, 3], seconds=2.0, trace_dir="somewhere", trace_seconds=0.1)
    timeline = Timeline([])
    for _, probe in probes.events():
        client.now = probe.__name__
        timeline._guard(probe)
    return probes.judge(timeline.errors, ALONE)


@pytest.mark.parametrize("silent, says, refused_for", [
    ((), {}, None),
    (("sample_slots",), {}, None),  # a per-layer reader's sample: a note
    (("snap_end",), {}, "snap_end"),
    (("snap_start",), {}, "snapshots"),
    (("canary_buffered",), {}, "canary"),
    ((), {"canary_stream": ALONE[:-1] + [99]}, "loaded_stream differs"),
    (("trace_stop",), {}, "trace_stop"),
    (("trace_start",), {}, "trace_start"),
], ids=["all_answer", "silent_sample", "silent_end_snapshot", "silent_start_snapshot", "silent_canary", "differing_canary", "silent_stop", "silent_start"])
def test_a_silent_sample_is_a_note_and_a_silent_snapshot_or_a_differing_canary_a_problem(silent, says, refused_for):
    problems, notes = probe_window(FakeClient(silent, says))
    if refused_for is None:
        assert problems == [], problems
    else:
        assert any(refused_for in p for p in problems), problems
    assert notes["slot_samples_missed"] == (4 if "sample_slots" in silent else 0)
    assert notes["slot_samples_taken"] == (0 if "sample_slots" in silent else 4)
    assert (notes["trace_stop_s"] is None) == ("trace_stop" in silent)


def test_every_deadline_that_can_meet_the_stall_comes_from_the_one_allowance():
    assert serve.TRACE_STALL_ALLOWANCE_S == 30.0
    assert serve.Client(None).probe_timeout == serve.PROBE_TIMEOUT_S == 60.0  # untraced: as ever


# ---- the routing judge on planted arrays: 2 layers, 6 rows, 8 experts, top 2
L, R, E, K = 2, 6, 8, 2
CLEAR = np.array([0.40, 0.30, 0.10, 0.08, 0.06, 0.03, 0.02, 0.01])
TIE = np.array([0.40, 0.20, 0.199, 0.08, 0.06, 0.03, 0.02, 0.01])  # 2nd and 3rd 0.5% apart
TIE_AT = (0, 2)


def planted(flip_to=None, flip_at=TIE_AT, short=False, extra=False, kv=None):
    """Reference probabilities with one near tie, the copies' routing (the
    reference's own top 2 everywhere), and an engine that agrees with the
    copies but for what is planted -> the judge's verdict."""
    probs = np.zeros((L, R, E))
    for li in range(L):
        for r in range(R):
            probs[li, r, np.roll(np.arange(E), li + r)] = TIE if (li, r) == TIE_AT else CLEAR
    order = np.argsort(-probs, -1, kind="stable")
    routing = order[..., :K]
    load = np.bincount(routing.reshape(-1), minlength=E)
    if flip_to is not None:  # the engine gave the row's 2nd choice up for another expert
        load[order[flip_at][K - 1]] -= 1
        load[order[flip_at][flip_to]] += 1
    if short:
        load[order[TIE_AT][K - 1]] -= 1
    if extra:
        load[order[TIE_AT][K]] += 1
    rng = np.random.default_rng(0)
    keys = rng.standard_normal((L, R, 4)).astype(np.float32)
    copy_keys = keys.copy()
    for (li, r), by in (kv or {}).items():
        copy_keys[li, r] += by
    return serve_moe.judge_copies(
        probs, routing, load, top_k=K, margin=0.03, expected_total=L * R * K, tokens=(5, 6), copy_tokens=(5, 6), token_rows=(R - 2, R - 1),
        pieces=[("keys", keys, copy_keys, 0.02, 0.15)])


@pytest.mark.parametrize("kwargs, ok, flips", [
    ({}, True, 0),
    ({"flip_to": K}, True, 1),  # inside the margin, between the k-th and the (k+1)-th: what a near tie is
    ({"flip_to": K, "flip_at": (0, 3)}, False, 1),  # the same flip in a row whose margin is clear
    ({"flip_to": K + 1}, False, 1),  # at the near tie, but to a third expert
    ({"short": True}, False, 0),  # a counter one row short
    ({"extra": True}, False, 0),  # a wrong total
    ({"flip_to": K, "kv": {(1, 2): 1e-3}}, True, 1),  # the copies' K/V an ulp off the engine's: inside the tolerances the program is held to
    ({"kv": {(0, 2): 1e-3}}, True, 0),  # and so in the first layer with nothing upstream: what the chip shows at some seeds
    ({"flip_to": K, "kv": {(1, 2): 2.0}}, False, 1),  # far outside them: other programs
], ids=["same", "flip_inside_margin", "flip_outside_margin", "flip_to_third_expert", "one_row_short", "wrong_total",
        "kv_an_ulp_off", "kv_an_ulp_off_first_layer", "kv_far_off"])
def test_the_compiled_forms_may_differ_only_where_program_and_reference_may(kwargs, ok, flips):
    out = planted(**kwargs)
    assert out["ok"] == ok, out
    assert out["flips"] == flips and out["near_tie_rows"] == 1
    if ok and flips:
        assert out["flip_margin"] == pytest.approx(0.005)  # the near tie that accounts for the flip


def test_tokens_may_differ_only_downstream_of_a_near_tie():
    probs = np.broadcast_to(CLEAR, (L, R, E)).copy()
    routing = np.broadcast_to(np.arange(K), (L, R, K))
    load = np.bincount(routing.reshape(-1), minlength=E)
    kw = dict(top_k=K, margin=0.03, expected_total=L * R * K, token_rows=(R - 1,), pieces=[])
    assert not serve_moe.judge_copies(probs, routing, load, tokens=(5,), copy_tokens=(6,), **kw)["ok"]
    probs[0, 0] = TIE  # the first row of the first layer: the next layer's mixer hands it to every later row
    assert serve_moe.judge_copies(probs, routing, load, tokens=(5,), copy_tokens=(6,), **kw)["ok"]


# ---- the wait for the chips
class Clock:
    def __init__(self):
        self.t = 0.0

    def sleep(self, s):
        self.t += s


@pytest.mark.parametrize("leaves_after, free, waited", [(2.0, True, (2.0, 2.5)), (None, False, (60.0, 60.5)), (0.0, True, (0.0, 0.0))], ids=["leaves_after_2s", "never_leaves", "nobody"])
def test_the_wait_for_the_chips_ends_when_the_holder_leaves_or_at_its_limit(leaves_after, free, waited):
    clock = Clock()
    holder = [(4242, "python3 benchmarks/run.py --workload other")]
    found = cluster.wait_for_chips(holders=lambda: holder if leaves_after is None or clock.t < leaves_after else [], clock=lambda: clock.t, sleep=clock.sleep)
    assert found["chips_free"] is free and waited[0] <= found["chips_wait_s"] <= waited[1]
    assert found["chip_holders"] == ([[4242, holder[0][1]]] if leaves_after != 0.0 else [])


def test_a_holder_is_another_process_with_a_chip_open(tmp_path):
    def process(pid, targets, cmdline=b"python3\0-m\0other"):
        os.makedirs(tmp_path / str(pid) / "fd")
        for i, target in enumerate(targets):
            os.symlink(target, tmp_path / str(pid) / "fd" / str(i))
        (tmp_path / str(pid) / "cmdline").write_bytes(cmdline)

    process(11, ["/dev/null", "/dev/vfio/3"])
    process(12, ["/tmp/libtpu_lockfile", "/dev/accel2"])
    process(14, ["/tmp/libtpu_lockfile"])  # the lock file alone is no chip: a killed holder leaves it behind
    process(13, ["/dev/vfio/vfio", "/dev/null"])  # the container node, not a chip
    process(os.getpid(), ["/dev/accel0"])  # this process is no OTHER process
    (tmp_path / "uptime").write_text("1 1")
    assert sorted(cluster.chip_holders(str(tmp_path))) == [(11, "python3 -m other"), (12, "python3 -m other")]


# ---- the one retry
class FakeCluster:
    def __init__(self):
        self.started = self.stopped = self.waited = 0

    def wait_for_chips(self):
        self.waited += 1
        return {"chips_wait_s": 0.0, "chip_holders": [], "chips_free": True}

    def start(self, chips, tiny):
        self.started += 1

    def stop(self):
        self.stopped += 1

    def session_dir(self):
        return ""

    def log_tails(self, session):
        return "----- tail of worker-1.log\nlibtpu: the chip is held\n"

    def assert_driver_off_jax(self):
        pass


class FakeDriver:
    """Raises in the attempts listed: ``before`` or ``inside`` the window."""

    def __init__(self, fails, correct=True):
        self.fails, self.correct, self.calls = dict(fails), correct, 0

    def run(self, ctx):
        self.calls += 1
        where = self.fails.get(self.calls)
        if where == "before":
            raise RuntimeError("worker died while running jax.devices()\nsecond line")
        ctx.window_opens()
        if where == "inside":
            raise RuntimeError("a request hung")
        return {"correct": self.correct, "notes": {}}


@pytest.mark.parametrize("fails, correct, calls, attempts", [
    ({}, True, 1, 1),
    ({1: "before"}, True, 2, 2),  # before the window: once more
    ({1: "before", 2: "before"}, True, 2, None),  # but once only
    ({1: "inside"}, True, 1, None),  # inside the window: never
    ({1: "before", 2: "inside"}, True, 2, None),
    ({}, False, 1, 1),  # a result that says correct: false is a result: never run again
], ids=["sound", "before_once", "before_twice", "inside", "before_then_inside", "incorrect_is_a_result"])
def test_a_failure_before_the_window_is_retried_once_and_no_other(tmp_path, capsys, fails, correct, calls, attempts):
    ctx = bench_run.Context({"chips": 1}, {}, {}, 1, 45.0, False, False, str(tmp_path), "")
    driver, fake = FakeDriver(fails, correct), FakeCluster()
    measured = bench_run.measure(ctx, driver, fake, "cell-s1-t0")
    assert driver.calls == calls and fake.started == fake.stopped == fake.waited == calls
    kept = tmp_path / "attempt1.err"
    if attempts is None:
        assert measured is None and "RuntimeError" in capsys.readouterr().err
    else:
        raw, n, first_error, waits = measured
        assert n == attempts and raw["correct"] is correct and len(waits) == attempts
        assert first_error == ("RuntimeError: worker died while running jax.devices()" if attempts == 2 else None)
    if fails.get(1) == "before":
        said = kept.read_text()
        assert "worker died while running jax.devices()" in said and "Traceback" in said and "libtpu: the chip is held" in said
    else:
        assert not kept.exists()
    assert bench_run.may_retry(1, False) and not bench_run.may_retry(2, False) and not bench_run.may_retry(1, True)


# ---- the collectives' reader: one pass over two sorted unions, the double loop it replaced as the reference
def covered_by_the_double_loop(coll, other):
    return sum(max(0.0, min(e, e2) - max(s, s2)) for s, e in coll for s2, e2 in other)


def exposed_by_the_double_loop(reduced, is_collective):
    ops = tr.leaf_events(reduced["first_device_whole_ops"])
    coll = tr.union_intervals((s, s + d) for name, s, d in ops if is_collective(name))
    other = tr.union_intervals((s, s + d) for name, s, d in ops if not is_collective(name))
    total = tr._total(coll)
    return total * 1e-9, (total - covered_by_the_double_loop(coll, other)) * 1e-9


def random_union(n, seed):
    rng = random.Random(seed)
    starts = [rng.uniform(0.0, 60.0 * n) for _ in range(n)]
    return tr.union_intervals((s, s + rng.expovariate(1 / 25.0) + 0.5) for s in starts)


def test_the_one_pass_is_the_double_loop_to_the_last_bit_and_fast():
    for seed in range(5):
        coll, other = random_union(200, seed), random_union(200, 100 + seed)
        covered = tr._total(tr.intersect_intervals(coll, other))
        assert covered == covered_by_the_double_loop(coll, other) and 0.0 < covered < tr._total(coll)
    coll, other = random_union(5000, 7), random_union(5000, 8)
    assert len(coll) > 3000 and len(other) > 3000
    t = time.perf_counter()
    covered = tr._total(tr.intersect_intervals(coll, other))
    assert time.perf_counter() - t < 1.0 and 0.0 < covered < tr._total(coll)


def test_exposed_seconds_reads_what_the_double_loop_read():
    rng = random.Random(3)
    ops, t = [], 0.0
    for i in range(400):
        t += rng.expovariate(1 / 40.0)
        ops.append((f"all-gather.{i}" if i % 3 == 0 else f"fusion.{i}", t + rng.uniform(-30, 30), rng.expovariate(1 / 60.0) + 1.0))
    reduced = {"first_device_whole_ops": ops}
    is_coll = lambda name: name.startswith("all-")  # noqa: E731
    assert tr.exposed_seconds(reduced, is_coll) == exposed_by_the_double_loop(reduced, is_coll)
