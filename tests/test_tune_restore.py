"""Tune experiment persistence: kill the driver mid-experiment, restore,
resume from checkpoints (reference: python/ray/tune/tuner.py:159
Tuner.restore + trial_runner experiment checkpointing)."""

import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAINABLE_MOD = '''
import time


def slow_trainable(config):
    from ray_tpu.air import session

    start = 0
    ckpt = session.get_checkpoint()
    if ckpt is not None:
        start = ckpt.to_dict()["iteration"]
    for i in range(start + 1, 9):
        time.sleep(0.3)
        from ray_tpu.air.checkpoint import Checkpoint

        session.report(
            {"loss": 1.0 / i, "iteration": i},
            checkpoint=Checkpoint.from_dict({"iteration": i}),
        )
'''

DRIVER = '''
import sys

sys.path.insert(0, {repo!r})
sys.path.insert(0, {tmp!r})

import ray_tpu
from ray_tpu import tune
from ray_tpu.air.config import RunConfig
from ray_tpu.tune.tuner import TuneConfig, Tuner
from trainable_mod import slow_trainable

ray_tpu.init(num_cpus=2)
tuner = Tuner(
    slow_trainable,
    param_space={{"lr": tune.grid_search([0.1, 0.2])}},
    tune_config=TuneConfig(metric="loss", mode="min", max_concurrent_trials=2),
    run_config=RunConfig(name="restore_exp", storage_path={tmp!r}),
)
tuner.fit()
'''


def test_kill_driver_and_restore(tmp_path):
    tmp = str(tmp_path)
    with open(os.path.join(tmp, "trainable_mod.py"), "w") as f:
        f.write(TRAINABLE_MOD)
    with open(os.path.join(tmp, "driver.py"), "w") as f:
        f.write(DRIVER.format(repo=REPO, tmp=tmp))

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, os.path.join(tmp, "driver.py")], env=env, cwd=REPO
    )
    state_file = os.path.join(tmp, "restore_exp", "experiment_state.pkl")

    # wait until at least one checkpointed report is persisted, then KILL
    import pickle

    deadline = time.time() + 120
    while time.time() < deadline:
        if os.path.exists(state_file):
            try:
                with open(state_file, "rb") as f:
                    st = pickle.load(f)
                if any(
                    t["latest_checkpoint"] is not None
                    and t["latest_checkpoint"]["iteration"] >= 2
                    for t in st["trials"]
                ):
                    break
            except Exception:
                pass
        if proc.poll() is not None:
            break
        time.sleep(0.2)
    assert proc.poll() is None, "driver finished before we could kill it"
    proc.send_signal(signal.SIGKILL)
    proc.wait()

    # cleanup the killed driver's cluster processes before starting ours
    subprocess.run(["pkill", "-f", "ray_tpu.gcs.head_main"], check=False)
    subprocess.run(["pkill", "-f", "ray_tpu.core.worker_main"], check=False)
    time.sleep(1.0)

    sys.path.insert(0, tmp)
    try:
        import ray_tpu
        from trainable_mod import slow_trainable
        from ray_tpu.tune.tuner import Tuner

        ray_tpu.init(num_cpus=2)
        try:
            tuner = Tuner.restore(
                os.path.join(tmp, "restore_exp"), slow_trainable
            )
            grid = tuner.fit()
            assert len(grid) == 2
            for t in grid.trials:
                assert t.state == "TERMINATED", (t.trial_id, t.state, t.error)
                assert t.last_metrics["iteration"] == 8
                # resumed, not restarted: restored history (1..k) continues
                # with k+1..8 — a from-scratch restart would re-report
                # iterations 1..k and leave duplicates
                iters = [h["iteration"] for h in t.history]
                assert iters == list(range(1, 9)), (t.trial_id, iters)
        finally:
            ray_tpu.shutdown()
    finally:
        sys.path.remove(tmp)
