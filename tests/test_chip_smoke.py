"""chip_smoke.py and the rules it rests on (ray_tpu/_private/tpu.py): chip
discovery without JAX, the one spawn-environment function, the placed
compile cache, one TPU worker per host, and teardown that waits for the
chips.  No test here touches a chip: a TPU worker inherits the suite's
JAX_PLATFORMS=cpu (conftest.py)."""

import importlib.util
import json
import logging
import os
import signal
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private import tpu

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _import(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------- cluster-free


def test_detect_chips_counts_device_nodes(tmp_path):
    assert tpu.detect_chips(str(tmp_path)) == 0
    (tmp_path / "vfio").mkdir()
    (tmp_path / "vfio" / "vfio").touch()  # the container node is not a chip
    assert tpu.detect_chips(str(tmp_path)) == 0
    for n in ("0", "1", "2", "3"):
        (tmp_path / "vfio" / n).touch()
    assert tpu.detect_chips(str(tmp_path)) == 4
    other = tmp_path / "kernel_driver"
    other.mkdir()
    (other / "accel0").touch()
    (other / "accelerometer").touch()
    assert tpu.detect_chips(str(other)) == 1


def test_compile_cache_dir_is_env_or_fixed_in_checkout():
    assert tpu.compile_cache_dir({"JAX_COMPILATION_CACHE_DIR": "/some/dir"}) == "/some/dir"
    assert tpu.compile_cache_dir({}) == os.path.join(REPO, ".jax_cache")
    assert tpu.compile_cache_dir({}) == tpu.compile_cache_dir({"HOME": "/elsewhere"})


def test_worker_spawn_env():
    base = {"PATH": "/bin", "JAX_PLATFORMS": "tpu,cpu", "RAY_TPU_WORKER_TPU": "1"}
    pool = tpu.worker_spawn_env(base, tpu=False)
    assert pool["JAX_PLATFORMS"] == "cpu" and "RAY_TPU_WORKER_TPU" not in pool
    assert "JAX_COMPILATION_CACHE_DIR" not in pool
    worker = tpu.worker_spawn_env(base, tpu=True)
    assert worker["JAX_PLATFORMS"] == "tpu,cpu" and worker["RAY_TPU_WORKER_TPU"] == "1"
    assert worker["JAX_COMPILATION_CACHE_DIR"] == os.path.join(REPO, ".jax_cache")
    # unset: the chip or an error, never jax's silent choice of the CPU backend
    assert tpu.worker_spawn_env({}, tpu=True)["JAX_PLATFORMS"] == "tpu"
    # the suite's switch: a cluster started under JAX_PLATFORMS=cpu stays there
    assert tpu.worker_spawn_env({"JAX_PLATFORMS": "cpu"}, tpu=True)["JAX_PLATFORMS"] == "cpu"
    placed = tpu.worker_spawn_env({"JAX_COMPILATION_CACHE_DIR": "/some/dir"}, tpu=True)
    assert placed["JAX_COMPILATION_CACHE_DIR"] == "/some/dir"
    assert base == {"PATH": "/bin", "JAX_PLATFORMS": "tpu,cpu", "RAY_TPU_WORKER_TPU": "1"}
    # nothing rides along: the marker, the platform and the cache directory
    assert set(tpu.worker_spawn_env({}, tpu=True)) == {
        "RAY_TPU_WORKER_TPU", "JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR",
    }
    assert set(tpu.worker_spawn_env({}, tpu=False)) == {"JAX_PLATFORMS"}


def test_reap_waits_for_the_process():
    proc = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    try:
        assert not tpu.wait_pid_exit(proc.pid, 0.05)
        proc.terminate()
        # unreaped, it lingers as a zombie: its files are closed, so it is gone
        assert tpu.reap_tpu_worker(proc.pid) is None
    finally:
        proc.kill()
        proc.wait()
    assert tpu.wait_pid_exit(proc.pid, 0.0)


_IGNORES_SIGTERM = "import signal, time; signal.signal(signal.SIGTERM, signal.SIG_IGN); print('ready', flush=True); time.sleep(60)"


def _reap_lines(caplog):
    return [json.loads(r.getMessage()) for r in caplog.records if "tpu_worker_reaped" in r.getMessage()]


def test_reap_says_how_long_the_worker_took(caplog):
    proc = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    try:
        proc.terminate()
        with caplog.at_level(logging.INFO, logger="ray_tpu._private.tpu"):
            assert tpu.reap_tpu_worker(proc.pid) is None
    finally:
        proc.kill()
        proc.wait()
    (line,) = _reap_lines(caplog)
    assert line["pid"] == proc.pid and line["sigkill"] is False and line["gone"] is True
    assert 0 <= line["seconds"] < tpu._EXIT_WAIT_S and line["chips"] == tpu.detect_chips()


def test_reap_kills_a_worker_that_ignores_sigterm_and_says_so(caplog, monkeypatch):
    monkeypatch.setattr(tpu, "_EXIT_WAIT_S", 0.3)
    proc = subprocess.Popen([sys.executable, "-c", _IGNORES_SIGTERM], stdout=subprocess.PIPE)
    try:
        assert proc.stdout.readline().strip() == b"ready"  # the handler is in place
        proc.terminate()
        with caplog.at_level(logging.INFO, logger="ray_tpu._private.tpu"):
            assert tpu.reap_tpu_worker(proc.pid) is None
        assert proc.wait(timeout=5) == -signal.SIGKILL
    finally:
        proc.kill()
        proc.wait()
    (line,) = _reap_lines(caplog)
    assert line["sigkill"] is True and line["gone"] is True and 0.3 <= line["seconds"] < 0.3 + tpu._KILL_WAIT_S


def test_reap_gives_up_only_after_both_waits_have_run_out(caplog, monkeypatch):
    """A worker that outlives SIGKILL (in the kernel, releasing its chips'
    mappings) cannot be made here; a kill that does not arrive stands in."""
    proc = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"])
    try:
        with monkeypatch.context() as m, caplog.at_level(logging.INFO, logger="ray_tpu._private.tpu"):
            m.setattr(tpu, "_EXIT_WAIT_S", 0.3)
            m.setattr(tpu, "_KILL_WAIT_S", 0.4)
            m.setattr(tpu, "REAP_WAIT_S", 0.7)
            m.setattr(tpu.os, "kill", lambda pid, sig: None)
            t0 = time.monotonic()
            err = tpu.reap_tpu_worker(proc.pid)
            took = time.monotonic() - t0
    finally:
        proc.kill()
        proc.wait()
    assert err is not None and f"pid {proc.pid}" in err and "still alive 1s" in err
    assert 0.7 <= took < 1.5
    (line,) = _reap_lines(caplog)
    assert line["sigkill"] is True and line["gone"] is False and line["seconds"] >= 0.7


@pytest.mark.skipif(tpu.detect_chips() > 0, reason="this host has chips: the smoke would run")
def test_smoke_exits_nonzero_fast_without_a_chip():
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "tpu"},
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode not in (0, None)
    assert time.time() - t0 < 30
    assert "no TPU chip" in proc.stderr
    assert proc.stdout.strip() == ""  # no result line, no model built


@pytest.mark.skipif(tpu.detect_chips() > 0, reason="this host has chips: the benchmark would run")
def test_benchmark_refuses_to_measure_a_cpu():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", "mistral-7b-l16.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env,
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 2
    assert "nothing was run" in proc.stderr
    assert proc.stdout.strip() == ""  # no result line


def test_one_peaks_table_and_no_default():
    from ray_tpu.train.jax.step_probe import peak_flops_per_device

    assert peak_flops_per_device("TPU v5 lite") == 197e12  # what a v5e calls itself
    assert peak_flops_per_device("TPU v4") == 275e12
    assert peak_flops_per_device("cpu") is None
    assert peak_flops_per_device("TPU v9 mega") is None


def test_attention_has_no_unreachable_impl():
    import jax.numpy as jnp

    from ray_tpu.ops import attention

    q = jnp.ones((1, 8, 2, 4))
    with pytest.raises(ValueError):
        attention.causal_attention(q, q, q, impl="flash")
    assert not hasattr(attention, "_flash")
    assert attention.causal_attention(q, q, q).shape == q.shape


def test_checks_refuse_a_cpu_report():
    """The checks are the smoke's exit code: a report from a CPU backend, a
    mesh that landed on one device, an einsum train step and a replica that
    sharded over chips it was not granted must each fail it."""
    smoke = _import("chip_smoke")
    good = {
        "platform": "tpu", "device_count": 4, "local_device_count": 4, "mesh": {"dp": 4},
        "per_chip_batch": 18, "batch_shards": [[i, 18] for i in range(4)], "vocab": 50257,
        "losses": [10.9, 10.5, 10.1], "pallas_calls": 3, "all_reduces": 9,
        "peak_bytes_in_use": 1 << 30, "cache_dir": "/c",
    }
    smoke.check_train(good, 4, "/c")
    for bad in (
        {"platform": "cpu"},
        {"device_count": 1, "local_device_count": 1},
        {"batch_shards": [[0, 72]]},
        {"batch_shards": [[0, 18]] * 4},
        {"losses": [10.9, 10.9, float("nan")]},
        {"losses": [10.9, 10.9, 11.0]},
        {"losses": [3.0, 2.0, 1.0]},
        {"pallas_calls": 0},
        {"all_reduces": 0},
        {"cache_dir": "/tmp/session_1/cache"},
    ):
        with pytest.raises(smoke.SmokeFailure):
            smoke.check_train({**good, **bad}, 4, "/c")
    serve = {
        "platform": "tpu", "tp": 4, "new_tokens": 2, "requests_sent": 3,
        "shards": {"total_bytes": 400, "per_device_bytes": {f"d{i}": 100 for i in range(4)}},
        "answers": [[1, 2], [3, 4]], "streamed": [1, 2],
        "engine": {"compile_prefill": 1, "compile_decode": 1},
    }
    smoke.check_serve(serve, 4)
    for bad in (
        {"platform": "cpu"},
        {"tp": 1},
        {"shards": {"total_bytes": 400, "per_device_bytes": {"d0": 400}}},
        {"answers": [[1, 2], [3]]},
        {"streamed": [9, 9]},
        {"engine": {"compile_prefill": 1, "compile_decode": 2}},
    ):
        with pytest.raises(smoke.SmokeFailure):
            smoke.check_serve({**serve, **bad}, 4)


def test_result_line_has_the_contract_keys_and_no_other():
    """The last line of stdout is read by a machine: ``ok`` and ``device``
    (``platform``, ``kind``, ``count``) exactly; the rest is the summary line."""
    smoke = _import("chip_smoke")
    train = {
        "platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 4, "model": "gpt2_124m",
        "seq": 1024, "per_chip_batch": 18, "mesh": {"dp": 4}, "steps": 5, "losses": [10.98, 10.03],
        "compile_s": 14.0, "step_ms": 167.0, "peak_bytes_in_use": 1 << 30, "pallas_calls": 3,
        "all_reduces": 9,
    }
    serve = {
        "model": "llama_3b", "tp": 4, "requests_sent": 3, "answers": [[1, 2], [3, 4]],
        "streamed": [1, 2], "ready_s": 40.0, "compile_s": 20.0, "delete_s": 2.5,
        "shards": {"per_device_bytes": {f"d{i}": 100 for i in range(4)}},
    }
    summary = smoke.summarize(4.0, "/c", train, serve)
    assert summary["serve"]["requests_answered"] == 3 and summary["serve"]["tokens_returned"] == 6
    assert summary["train"]["attention"] == "splash" and summary["cache_dir"] == "/c"
    line = smoke.result_line(summary)
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 4},
    }


# ------------------------------------------------------------ with a cluster


def test_one_tpu_worker_per_host_and_kill_waits_for_it(shutdown_only):
    """Two actors that each ask for one chip of a two-chip host: the second
    is refused with an error the driver sees.  Killing the first returns
    once its process is gone, and the chips then serve the next request."""
    ray_tpu.init(num_cpus=2, num_tpus=2)

    @ray_tpu.remote(num_tpus=1)
    class Holder:
        def where(self):
            return os.getpid(), os.environ["JAX_PLATFORMS"], os.environ["JAX_COMPILATION_CACHE_DIR"]

    @ray_tpu.remote(num_tpus=1)
    def task_pid():
        return os.getpid()

    first = Holder.remote()
    pid, platform, cache = ray_tpu.get(first.where.remote(), timeout=60)
    assert platform == "cpu"  # inherited from the suite, not popped
    assert cache == tpu.compile_cache_dir()
    t0 = time.time()
    with pytest.raises(ray_tpu.exceptions.RayActorError, match=f"TpuBusyError.*pid {pid}"):
        ray_tpu.get(Holder.remote().where.remote(), timeout=60)
    with pytest.raises(Exception, match="already owns this host's chips"):
        ray_tpu.get(task_pid.remote(), timeout=60)
    assert time.time() - t0 < 20
    ray_tpu.kill(first)
    assert tpu.wait_pid_exit(pid, 0.0), "kill returned before the TPU worker exited"
    second = Holder.remote()
    pid2 = ray_tpu.get(second.where.remote(), timeout=60)[0]
    assert pid2 != pid
    ray_tpu.kill(second)
    # with no actor holding them, chip tasks share the one TPU worker
    assert ray_tpu.get(task_pid.remote(), timeout=60) == ray_tpu.get(task_pid.remote(), timeout=60)


@pytest.mark.slow
def test_smoke_phases_on_cpu_tiny(monkeypatch, shutdown_only):
    """chip_smoke.py's control flow end to end — both phases through the
    product entry points, the train worker gone before the replica starts —
    at the tiny sizes, on two virtual CPU devices.  What differs on the chip
    is what check_* then demand."""
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
    smoke = _import("chip_smoke")
    ray_tpu.init(num_cpus=4, num_tpus=2)
    train = smoke.run_train(2, model="tiny", per_chip_batch=2, warmup=1, steps=3)
    json.dumps(train)  # plain numbers only: the driver must not rebuild jax arrays
    assert train["device_count"] == 2 and train["mesh"] == {"dp": 2}
    assert [rows for _, rows in train["batch_shards"]] == [2, 2]
    assert len(train["losses"]) == 4 and train["losses"][-1] < train["losses"][0]
    assert train["all_reduces"] > 0 and train["pallas_calls"] == 0
    with pytest.raises(smoke.SmokeFailure, match="platform 'cpu'"):
        smoke.check_train(train, 2, train["cache_dir"])
    serve = smoke.run_serve(2, model="tiny", prompt_lens=[16, 200, 40, 5], new_tokens=8)
    json.dumps(serve)
    assert serve["tp"] == 2 and len(serve["shards"]["per_device_bytes"]) == 2
    assert [len(a) for a in serve["answers"]] == [8] * 4
    assert serve["streamed"] == serve["answers"][0]
    with pytest.raises(smoke.SmokeFailure, match="platform 'cpu'"):
        smoke.check_serve(serve, 2)
