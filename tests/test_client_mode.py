"""Ray-Client-mode remote driver: a driver with NO mmap of any node's
store (reference analog: python/ray/util/client/ — remote drivers proxy
object payloads over the control connection).  Simulated by a subprocess
driver with RAY_TPU_FORCE_CLIENT=1 connecting to a Cluster head."""

import os
import subprocess
import sys
import textwrap

import pytest

from ray_tpu.cluster_utils import Cluster


def test_client_driver_full_api():
    c = Cluster(initialize_head=True, head_node_args={"num_cpus": 4})
    try:
        script = textwrap.dedent(
            f"""
            import numpy as np
            import ray_tpu

            ray_tpu.init(address="{c.address}")
            from ray_tpu._private.worker import global_worker
            assert global_worker.core_worker.is_client, "client mode not engaged"
            assert global_worker.core_worker.store is None

            # put/get through the head proxy
            ref = ray_tpu.put(np.arange(1000.0))
            assert float(ray_tpu.get(ref, timeout=60).sum()) == 499500.0

            # tasks with large args + large results
            @ray_tpu.remote
            def double(a):
                return a * 2

            out = ray_tpu.get(double.remote(np.ones(300_000)), timeout=120)
            assert out.shape == (300_000,) and float(out[0]) == 2.0

            # actors (direct calls work over TCP from a client too)
            @ray_tpu.remote
            class Counter:
                def __init__(self):
                    self.n = 0
                def add(self, k):
                    self.n += k
                    return self.n

            cnt = Counter.remote()
            assert ray_tpu.get([cnt.add.remote(2) for _ in range(5)][-1], timeout=60) == 10

            # wait() without a local store
            refs = [double.remote(np.ones(10)) for _ in range(4)]
            ready, rest = ray_tpu.wait(refs, num_returns=2, timeout=60)
            assert len(ready) >= 2

            print("CLIENT-MODE-OK")
            """
        )
        env = dict(os.environ)
        env["RAY_TPU_FORCE_CLIENT"] = "1"
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-c", script],
            env=env,
            capture_output=True,
            text=True,
            timeout=300,
        )
        assert proc.returncode == 0, f"client driver failed:\n{proc.stderr[-3000:]}"
        assert "CLIENT-MODE-OK" in proc.stdout
    finally:
        c.shutdown()
