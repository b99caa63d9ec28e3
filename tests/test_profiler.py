"""Cluster-wide sampling profiler (ray_tpu/_private/profiler.py +
util/profile_api.py): off-path contract, hot-function dominance,
cluster-wide arm/disarm + collection across roles, timeline merge, the
≤5% overhead bound on a tracked ray_perf pair, stack dumps, and the
deprecated RAY_TPU_HEAD_PROFILE alias."""

import os
import threading
import time

import pytest


def _hot_spin(duration_s: float) -> int:
    """The planted hot function: pure-python arithmetic, so every sample
    of the executing thread lands inside this frame."""
    end = time.time() + duration_s
    x = 0
    while time.time() < end:
        for i in range(2000):
            x += i * i
    return x


# ------------------------------------------------------------ module unit


def test_hot_function_dominates_unit():
    """In-process: a busy thread's folded stacks are dominated by the
    planted hot function, idle runtime threads are filtered, and the
    sampler's own duty cycle stays inside the overhead contract."""
    from ray_tpu._private import profiler

    profiler.maybe_init_from_env("worker")
    assert profiler.aware()
    frames = []
    profiler.set_emitter(frames.append)
    t = threading.Thread(target=_hot_spin, args=(1.4,), name="hot", daemon=True)
    t.start()
    try:
        assert profiler.arm(hz=100)
        assert profiler.sampling()
        time.sleep(1.2)
        totals = profiler.local_totals()
        st = profiler.status()
    finally:
        profiler.disarm()
        profiler.set_emitter(None)
        t.join(timeout=5)
    assert not profiler.sampling()
    total = sum(totals.values())
    hot = sum(n for k, n in totals.items() if "_hot_spin@" in k)
    assert total > 30, f"sampler barely ran: {total} samples"
    assert hot / total >= 0.3, f"hot fn only {hot}/{total} of samples"
    # folded roots carry role;pid;thread synthetic frames
    key = next(k for k in totals if "_hot_spin@" in k)
    role, pid, thread = key.split(";")[:3]
    assert role == "worker" and int(pid) == os.getpid() and thread == "hot"
    # the sampler accounts its own cost; 100Hz must sit far under 5%
    assert st["duty_cycle"] < 0.05
    # deltas were shipped batched (≥1 flush window), never per sample
    assert frames and all("stacks" in f for f in frames)
    assert len(frames) < total


def test_off_path_hard_disabled(monkeypatch):
    """RAY_TPU_PROFILER=0 excises the plane: not aware, arm() refuses,
    thread-role tagging is a no-op, no sampler thread exists."""
    from ray_tpu._private import profiler

    monkeypatch.setenv("RAY_TPU_PROFILER", "0")
    profiler.maybe_init_from_env("worker")
    try:
        assert not profiler.aware()
        assert not profiler.arm(hz=100)
        assert not profiler.sampling()
        before = dict(profiler._thread_roles)
        profiler.set_thread_role("engine")
        assert profiler._thread_roles == before
        profiler.apply_ctrl({"op": "arm", "hz": 100})
        assert not profiler.sampling()
        assert not any(
            th.name == "ray_tpu-profiler" for th in threading.enumerate()
        )
    finally:
        monkeypatch.delenv("RAY_TPU_PROFILER", raising=False)
        profiler.maybe_init_from_env("driver")  # restore default awareness


def test_role_filtered_arm_applies_when_thread_role_registers_later():
    """A role-filtered arm that lands BEFORE the thread registers its
    role (engine loop still starting) must take effect when the role
    appears — `--role engine` works regardless of ordering."""
    from ray_tpu._private import profiler

    profiler.maybe_init_from_env("worker")
    profiler.set_emitter(None)
    try:
        profiler.apply_ctrl({"op": "arm", "hz": 100, "roles": ["engine"]})
        assert not profiler.sampling()  # no engine role here yet: sat out
        profiler.set_thread_role("engine")
        assert profiler.sampling()  # registration re-applied the ctrl
        # after a disarm, registering another role must NOT re-arm
        profiler.apply_ctrl({"op": "disarm"})
        profiler.set_thread_role("dashboard")
        assert not profiler.sampling()
    finally:
        profiler.apply_ctrl({"op": "disarm"})
        with profiler._lock:
            profiler._thread_roles.clear()


def test_lifetime_totals_survive_disarm_cycles():
    """The RAY_TPU_HEAD_PROFILE exit dump reads lifetime totals: a
    mid-run disarm (any cluster snapshot) retires the sampler but must
    not discard what it had accumulated."""
    from ray_tpu._private import profiler

    profiler.maybe_init_from_env("head")
    profiler.set_emitter(None)
    t = threading.Thread(target=_hot_spin, args=(1.0,), daemon=True)
    t.start()
    try:
        assert profiler.arm(hz=200)
        time.sleep(0.5)
        profiler.disarm()
        assert profiler.local_totals() == {}  # current-sampler view empty
        lifetime = profiler.local_totals(lifetime=True)
        assert sum(lifetime.values()) > 0
        # a second arm/disarm cycle accumulates, never resets
        assert profiler.arm(hz=200)
        time.sleep(0.3)
        profiler.disarm()
        again = profiler.local_totals(lifetime=True)
        assert sum(again.values()) >= sum(lifetime.values())
    finally:
        profiler.disarm()
        t.join(timeout=5)
        profiler.maybe_init_from_env("driver")


def test_folded_text_and_share_helpers():
    from ray_tpu._private import profiler
    from ray_tpu.util import profile_api

    stacks = {"worker;1;t;a@f:1;b@f:2": 3, "worker;1;t;c@f:3": 1}
    text = profiler.folded_text(stacks)
    lines = text.strip().splitlines()
    assert lines[0] == "worker;1;t;a@f:1;b@f:2 3"  # count-descending
    assert profile_api.sample_share(stacks, "b@f:2") == pytest.approx(0.75)
    assert profile_api.sample_share({}, "x") == 0.0
    # single-node collections keep the bare role;pid;thread roots
    merged = profile_api.folded_text({"w|n1": stacks, "x|n1": {"worker;1;t;c@f:3": 2}})
    assert "worker;1;t;c@f:3 3" in merged
    # multi-node collections join the node into the roots: pids are only
    # unique per host, so identical role;pid stacks must NOT conflate
    multi = profile_api.folded_text(
        {"w|n1": {"worker;1;t;c@f:3": 1}, "w|n2": {"worker;1;t;c@f:3": 2}}
    )
    assert "worker;n1;1;t;c@f:3 1" in multi
    assert "worker;n2;1;t;c@f:3 2" in multi


# --------------------------------------------------------------- cluster


def test_cluster_snapshot_three_roles(shutdown_only):
    """The acceptance shape: a snapshot against a live cluster running a
    busy actor + a tiny LLM engine returns collapsed stacks for ≥3
    distinct roles (head, worker, engine), with the planted hot function
    ≥30% of its process's samples; the sampled slices merge into the
    chrome timeline and the ray_tpu_profiler_* metric families exist."""
    import jax.numpy as jnp

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import engine_llm_deployment
    from ray_tpu.util import profile_api

    ray_tpu.init(num_cpus=3)
    try:
        cfg = LlamaConfig(
            dim=32, n_layers=1, n_heads=2, n_kv_heads=2, hidden_dim=64,
            vocab_size=128, compute_dtype=jnp.float32, max_seq_len=32,
        )
        dep = engine_llm_deployment(
            cfg, new_tokens=8, num_slots=2, page_size=4, prefill_chunk=4,
            num_tpus=0, tp=1, name="prof_llm",
        )
        handle = serve.run(dep.bind())
        ray_tpu.get(handle.remote({"prompt": [1, 2]}), timeout=600)  # compile

        @ray_tpu.remote
        class Busy:
            def burn(self, secs):
                return _hot_spin(secs)

        busy = Busy.remote()
        burn_ref = busy.burn.remote(6.0)

        # engine + head stay busy through the whole armed window
        stop = threading.Event()

        def engine_churn():
            while not stop.is_set():
                try:
                    ray_tpu.get(
                        handle.remote({"prompt": [3, 4, 5]}), timeout=120
                    )
                except Exception:  # noqa: BLE001 -- teardown race at test end
                    return

        churner = threading.Thread(target=engine_churn, daemon=True)
        churner.start()
        try:
            profile_api.start(clear=True)
            time.sleep(2.5)
            profile_api.stop()
        finally:
            stop.set()
        time.sleep(1.0)  # final fire-and-forget flushes land at the head
        stacks = profile_api.collect()
        churner.join(timeout=30)
        ray_tpu.get(burn_ref, timeout=60)

        roles = {bucket.split("|")[0] for bucket in stacks}
        assert {"head", "worker", "engine"} <= roles, f"roles seen: {roles}"

        # planted hot function ≥30% of ITS PROCESS's samples (folded keys
        # carry the pid as the second synthetic root frame)
        per_pid = {}
        for bucket, per in stacks.items():
            if not bucket.startswith("worker|"):
                continue
            for folded, n in per.items():
                pid = folded.split(";")[1]
                tot, hot = per_pid.get(pid, (0, 0))
                per_pid[pid] = (tot + n, hot + (n if "_hot_spin@" in folded else 0))
        assert per_pid, "no worker-role stacks collected"
        best = max(per_pid.values(), key=lambda th: th[1])
        assert best[1] > 0, "hot function never sampled"
        assert best[1] / best[0] >= 0.3, (
            f"hot fn {best[1]}/{best[0]} of its process's samples"
        )

        # timeline merge: sampled-stack slices render as cat=profile spans
        events = ray_tpu.timeline()
        prof = [e for e in events if e.get("cat") == "profile"]
        assert prof, "no profile slices on the timeline"
        assert all("top_stacks" in e["args"] for e in prof)
        slice_roles = {e["args"]["role"] for e in prof}
        assert {"head", "worker"} <= slice_roles

        # metric families aggregated at the head
        from ray_tpu.util import metrics as metrics_mod

        merged = metrics_mod.read_all()
        samples = {
            k: v for k, v in merged.items()
            if k.startswith("ray_tpu_profiler_samples_total")
        }
        assert samples and any(v.get("value", 0) > 0 for v in samples.values())
        sample_roles = {v["tags"].get("role") for v in samples.values()}
        assert {"head", "worker", "engine"} <= sample_roles
        overhead = [
            v for k, v in merged.items()
            if k.startswith("ray_tpu_profiler_overhead_ratio")
        ]
        assert overhead and all(v.get("value", 0) < 0.05 for v in overhead)
    finally:
        try:
            serve.shutdown()
        except Exception:  # noqa: BLE001 -- scrape assertions already ran; teardown is best-effort
            pass


def test_arm_disarm_e2e_and_stack_dumps(shutdown_only):
    """Runtime arm reaches every process over the pubsub fan-out, disarm
    freezes the aggregation even while the cluster stays busy, and
    `ray-tpu stacks` (stack_dumps) harvests all-thread tracebacks from
    multiple roles."""
    import ray_tpu
    from ray_tpu.util import profile_api

    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    class Busy:
        def burn(self, secs):
            return _hot_spin(secs)

    busy = Busy.remote()
    ref = busy.burn.remote(8.0)

    st = profile_api.start(clear=True)
    assert st.get("armed") or st.get("ok")
    time.sleep(1.5)
    mid = profile_api.status()
    assert mid["armed"]
    assert sum(a["samples"] for a in mid["aggregate"].values()) > 0
    profile_api.stop()
    time.sleep(1.0)
    frozen = profile_api.collect()
    total_frozen = sum(sum(v.values()) for v in frozen.values())
    assert total_frozen > 0
    time.sleep(1.2)  # cluster still busy (burn running) but disarmed
    again = profile_api.collect()
    assert sum(sum(v.values()) for v in again.values()) == total_frozen

    dumps = profile_api.stack_dumps(settle=1.5)
    dump_roles = {d["role"] for d in dumps}
    assert {"head", "worker"} <= dump_roles, f"dump roles: {dump_roles}"
    worker_dump = next(d for d in dumps if d["role"] == "worker")
    assert "thread" in worker_dump["text"] and worker_dump["pid"] > 0
    ray_tpu.get(ref, timeout=60)


def _task_pair_rate(ray_tpu, tiny, seconds=0.8):
    """The tracked `tasks async batch 100`-shaped pair from ray_perf:
    batched .remote() bursts drained with one get."""
    t0 = time.perf_counter()
    done = 0
    while time.perf_counter() - t0 < seconds:
        ray_tpu.get([tiny.remote(i) for i in range(50)], timeout=60)
        done += 50
    return done / (time.perf_counter() - t0)


def test_overhead_bound_on_tracked_pair(shutdown_only):
    """The ≤5% contract: the armed profiler (default hz) costs ≤5% on
    the tracked ray_perf task-batch pair.  Interleaved best-of trials
    absorb box noise; the sampler's own duty-cycle accounting (the cost
    it CAN impose) is asserted strictly, and the wall-clock A/B gets one
    re-measure before failing so a scheduler hiccup can't flake CI."""
    import ray_tpu
    from ray_tpu._private import profiler
    from ray_tpu.util import profile_api

    ray_tpu.init(num_cpus=2)

    @ray_tpu.remote
    def tiny(i):
        return i

    _task_pair_rate(ray_tpu, tiny, seconds=1.0)  # warm pool + leases

    def compare():
        rates_off, rates_on = [], []
        for _ in range(2):
            rates_off.append(_task_pair_rate(ray_tpu, tiny))
            profile_api.start(clear=True)
            rates_on.append(_task_pair_rate(ray_tpu, tiny))
            duty = profiler.status().get("duty_cycle", 0.0)
            profile_api.stop()
            assert duty < 0.05, f"sampler duty cycle {duty:.2%} breaks the contract"
        return max(rates_on), max(rates_off)

    best_on, best_off = compare()
    if best_on < 0.95 * best_off:
        best_on, best_off = compare()  # one re-measure: noise, not policy
    assert best_on >= 0.95 * best_off, (
        f"armed profiler cost {1 - best_on / best_off:.1%} "
        f"({best_on:.0f}/s armed vs {best_off:.0f}/s off)"
    )


def test_head_profile_env_alias(shutdown_only, tmp_path):
    """RAY_TPU_HEAD_PROFILE survives as a deprecated alias: it arms
    head-role sampling at startup and writes collapsed stacks (not
    cProfile pstats) to the path on head exit."""
    import ray_tpu

    out = tmp_path / "head.folded"
    os.environ["RAY_TPU_HEAD_PROFILE"] = str(out)
    try:
        ray_tpu.init(num_cpus=1)

        @ray_tpu.remote
        def tiny(i):
            return i

        # head-path traffic so the armed head sampler sees non-idle stacks
        ray_tpu.get([tiny.remote(i) for i in range(200)], timeout=120)
        time.sleep(1.0)
        ray_tpu.shutdown()
        deadline = time.time() + 15
        while time.time() < deadline and not out.exists():
            time.sleep(0.2)
        assert out.exists(), "alias wrote no folded-stack dump at head exit"
        text = out.read_text()
        assert text.strip(), "folded dump is empty"
        first = text.splitlines()[0]
        assert first.startswith("head;") and first.rsplit(" ", 1)[1].isdigit()
    finally:
        os.environ.pop("RAY_TPU_HEAD_PROFILE", None)
