"""Core task/object API tests.

Modeled on the reference's python/ray/tests/test_basic.py tier: submit,
get, put, wait, errors, nesting, dependencies, options.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import GetTimeoutError


@ray_tpu.remote
def add(a, b):
    return a + b


@ray_tpu.remote
def echo(x):
    return x


def test_simple_task(ray_start_regular):
    assert ray_tpu.get(add.remote(1, 2), timeout=60) == 3


def test_many_parallel_tasks(ray_start_regular):
    refs = [add.remote(i, i) for i in range(50)]
    assert ray_tpu.get(refs, timeout=120) == [2 * i for i in range(50)]


def test_put_get_roundtrip(ray_start_regular):
    for value in [1, "s", None, {"a": [1, 2]}, (1, 2), b"bytes", 3.14]:
        assert ray_tpu.get(ray_tpu.put(value)) == value


def test_put_numpy_zero_copy(ray_start_regular):
    arr = np.random.rand(512, 512)
    got = ray_tpu.get(ray_tpu.put(arr))
    np.testing.assert_array_equal(arr, got)
    # zero-copy: the result is backed by the shm mapping, not a fresh heap copy
    assert not got.flags["OWNDATA"]


def test_object_ref_as_argument(ray_start_regular):
    ref = ray_tpu.put(10)
    assert ray_tpu.get(add.remote(ref, 5), timeout=60) == 15


def test_task_output_as_argument(ray_start_regular):
    a = add.remote(1, 1)
    b = add.remote(a, 1)
    c = add.remote(a, b)
    assert ray_tpu.get(c, timeout=60) == 5


def test_task_error_propagates(ray_start_regular):
    @ray_tpu.remote
    def fail():
        raise ZeroDivisionError("boom")

    with pytest.raises(ZeroDivisionError):
        ray_tpu.get(fail.remote(), timeout=60)


def test_error_contagion(ray_start_regular):
    @ray_tpu.remote
    def fail():
        raise ValueError("original")

    downstream = echo.remote(fail.remote())
    with pytest.raises(ValueError):
        ray_tpu.get(downstream, timeout=60)


def test_get_timeout(ray_start_regular):
    @ray_tpu.remote
    def slow():
        import time

        time.sleep(30)

    with pytest.raises(GetTimeoutError):
        ray_tpu.get(slow.remote(), timeout=0.5)


def test_wait(ray_start_regular):
    import time

    @ray_tpu.remote
    def fast():
        return 1

    @ray_tpu.remote
    def slow():
        time.sleep(20)
        return 2

    refs = [fast.remote(), slow.remote()]
    ready, not_ready = ray_tpu.wait(refs, num_returns=1, timeout=30)
    assert len(ready) == 1 and len(not_ready) == 1
    assert ready[0] == refs[0]


def test_nested_tasks(ray_start_regular):
    @ray_tpu.remote
    def outer(x):
        return ray_tpu.get(add.remote(x, 100), timeout=60)

    assert ray_tpu.get(outer.remote(1), timeout=120) == 101


def test_multiple_returns(ray_start_regular):
    @ray_tpu.remote(num_returns=3)
    def three():
        return 1, 2, 3

    a, b, c = three.remote()
    assert ray_tpu.get([a, b, c], timeout=60) == [1, 2, 3]


def test_options_override(ray_start_regular):
    r = add.options(num_cpus=2).remote(3, 4)
    assert ray_tpu.get(r, timeout=60) == 7


def test_large_arg_spills_to_store(ray_start_regular):
    arr = np.zeros(2_000_000, dtype=np.uint8)  # > inline threshold
    got = ray_tpu.get(echo.remote(arr), timeout=60)
    assert got.nbytes == arr.nbytes


def test_kwargs(ray_start_regular):
    @ray_tpu.remote
    def kw(a, b=2, c=3):
        return a + b + c

    assert ray_tpu.get(kw.remote(1, c=10), timeout=60) == 13


def test_cluster_resources(ray_start_regular):
    res = ray_tpu.cluster_resources()
    assert res.get("CPU") == 4.0
    avail = ray_tpu.available_resources()
    assert avail.get("CPU", 0) <= res["CPU"]


def test_cancel_queued_task(ray_start_regular):
    @ray_tpu.remote
    def blocker():
        import time

        time.sleep(60)

    # saturate the 4 cpus, then queue one more and cancel it
    blockers = [blocker.remote() for _ in range(4)]
    victim = blocker.remote()
    import time

    time.sleep(1.0)
    ray_tpu.cancel(victim)
    with pytest.raises(ray_tpu.exceptions.RayError):
        ray_tpu.get(victim, timeout=30)
    del blockers


def test_runtime_context(ray_start_regular):
    ctx = ray_tpu.get_runtime_context()
    assert ctx.get_node_id()

    @ray_tpu.remote
    def inside():
        from ray_tpu.runtime_context import get_runtime_context

        return get_runtime_context().task_id is not None

    assert ray_tpu.get(inside.remote(), timeout=60)


def test_oom_policy_kills_retriable_worker(monkeypatch, shutdown_only):
    """Under (forced) memory pressure the head kills a worker running a
    retriable task — never the last attempt, so the task still completes
    (reference analog: raylet worker_killing_policy.cc retriable-FIFO)."""
    import time as _time

    monkeypatch.setenv("RAY_TPU_TEST_FORCE_MEMORY_PRESSURE", "1")
    monkeypatch.setenv("RAY_TPU_MEMORY_MONITOR_INTERVAL_S", "0.5")
    import ray_tpu

    ray_tpu.init(num_cpus=2)

    import tempfile

    marker = tempfile.mktemp(prefix="oom_attempts_")

    @ray_tpu.remote(max_retries=1)
    def sleepy(path):
        import os as _os
        import time as _t

        with open(path, "a") as f:
            f.write("x")
        _t.sleep(2.0)
        return _os.getpid()

    import os as _os

    ref = sleepy.remote(marker)
    # first attempt gets OOM-killed (retries_left 1), the retry has
    # retries_left 0 and is spared, so the call completes
    pid = ray_tpu.get(ref, timeout=120)
    assert pid > 0
    # the kill REALLY happened: the task body started twice
    assert _os.path.getsize(marker) == 2, "OOM policy never killed the first attempt"
    _os.unlink(marker)


def test_ref_release_takes_no_lock(ray_start_regular):
    """The collector runs ``ObjectRef.__del__`` on whatever thread it
    interrupts, also one that is inside ``_add_local_ref`` and holds the
    ref-count lock: the release must not take that lock (a plain one, so the
    thread would wait for itself); the flush loop counts it down later."""
    import threading
    import time

    from ray_tpu._private.worker import global_worker

    cw = global_worker.core_worker
    ref = ray_tpu.put(1)
    oid = ref._id
    n_before = cw._local_refs.get(oid, 0)
    cw._add_local_ref(oid)
    assert cw._refs_lock.acquire(timeout=5)
    try:
        release = threading.Thread(target=cw._remove_local_ref, args=(oid,))  # what __del__ calls
        release.start()
        release.join(5)
        assert not release.is_alive(), "a release waited for the ref-count lock"
        assert cw._local_refs[oid] == n_before + 1
    finally:
        cw._refs_lock.release()
    deadline = time.time() + 5
    while cw._local_refs.get(oid, 0) != n_before and time.time() < deadline:
        time.sleep(0.05)
    assert cw._local_refs.get(oid, 0) == n_before
