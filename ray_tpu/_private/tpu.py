"""What this host's TPU chips are and who gets them.

libtpu gives every chip of a host to the first process that initialises
it and refuses the next one (``/tmp/libtpu_lockfile``), so the rule for
the whole runtime is: the driver, the head and the raylets never
initialise a JAX backend, and at most one TPU worker per host does.
Everything that enforces the rule reads it from here:

- ``detect_chips`` counts the accelerator device nodes, so the head learns
  its ``TPU`` resource without importing JAX;
- ``worker_spawn_env`` is the one place a worker's platform is decided
  (gcs/server.py for the head's node, raylet/raylet_main.py for the others);
- ``compile_cache_dir`` places JAX's persistent compilation cache;
- ``reap_tpu_worker`` is how kill/shutdown know a TPU worker has let go of
  the chips: they are free the moment its process is gone (measured on a
  v5e: a new process opens them 0.0 s after the old one exits, killed or
  not), and no sooner.
"""

from __future__ import annotations

import glob
import json
import logging
import os
import signal
import time
from typing import Dict, Mapping, Optional

logger = logging.getLogger(__name__)

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# How long a signalled TPU worker gets to exit before SIGKILL, and then to
# die.  From the ``tpu_worker_reaped`` lines of 15 teardowns on a v5e (7 on
# one chip, 8 on the four-chip host, PERF.md section 7, PR 28): libtpu's
# SIGTERM handler lets go in 2.1-2.5 s, once in 7.1 s, so 10 s is over what
# was seen.  The wait after SIGKILL is NOT measured: none of those teardowns
# needed the SIGKILL.  All that is known is one line of PR 26's log, a
# four-chip worker still alive 5 s after it (in the kernel, releasing its
# chips' mappings), which failed a finished run.  40 s is a guess with room
# for that one case, kept under the 60 s of the generic RPC timeout that
# callers of kill wait with; set it from ``tpu_worker_reaped`` lines with
# ``sigkill`` true once there are some.
_EXIT_WAIT_S = 10.0
_KILL_WAIT_S = 40.0
REAP_WAIT_S = _EXIT_WAIT_S + _KILL_WAIT_S


def detect_chips(dev_root: str = "/dev") -> int:
    """TPU chips this host exposes: one ``accel*`` node per chip on the
    kernel-driver images, one numbered VFIO group per chip on the
    pass-through ones (``vfio/vfio`` is the container node, not a chip)."""
    accel = glob.glob(os.path.join(dev_root, "accel[0-9]*"))
    vfio = [
        p
        for p in glob.glob(os.path.join(dev_root, "vfio", "*"))
        if os.path.basename(p).isdigit()
    ]
    return len(accel) + len(vfio)


def compile_cache_dir(environ: Mapping[str, str] = os.environ) -> str:
    """Where compiled programs are kept between processes.  JAX reads
    ``JAX_COMPILATION_CACHE_DIR`` itself, so a value set from outside is
    returned untouched; otherwise one fixed directory beside the package —
    the path is part of the cache key, so it must not move between runs."""
    return environ.get(_CACHE_ENV) or os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
        ".jax_cache",
    )


def worker_spawn_env(base: Mapping[str, str], tpu: bool) -> Dict[str, str]:
    """Environment of a worker process, from its spawner's.

    A pool worker is pinned to the CPU backend.  A TPU worker inherits
    ``JAX_PLATFORMS`` when the cluster was started with one — that is how
    tests keep TPU workers off a real chip (``JAX_PLATFORMS=cpu``) — and is
    otherwise told ``tpu``, so that a missing or busy chip is an error in
    the worker and never a silent run on the CPU backend."""
    env = dict(base)
    if tpu:
        env["RAY_TPU_WORKER_TPU"] = "1"
        env.setdefault("JAX_PLATFORMS", "tpu")
        env[_CACHE_ENV] = compile_cache_dir(env)
    else:
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("RAY_TPU_WORKER_TPU", None)
    return env


def wait_pid_exit(pid: int, timeout: float) -> bool:
    """True once process ``pid`` on this host has exited (a zombie has
    already closed its device files), False if it is still there after
    ``timeout`` seconds."""
    deadline = time.monotonic() + timeout
    while True:
        try:
            with open(f"/proc/{pid}/stat", "rb") as f:
                # "pid (comm) S ..." — comm may hold spaces and parentheses
                if f.read().rsplit(b")", 1)[1].split()[0] == b"Z":
                    return True
        except (FileNotFoundError, ProcessLookupError):
            return True
        if time.monotonic() >= deadline:
            return False
        # graftsan: disable=GS001 -- the head runs this on an executor thread; Raylet.shutdown calls it as its loop's last act, when nothing is left to serve
        time.sleep(0.01)


def reap_tpu_worker(pid: int) -> Optional[str]:
    """Wait for an already signalled TPU worker on this host to exit,
    escalating to SIGKILL.  Returns None once it is gone, or the error to
    show the caller: its chips stay taken for as long as it lives.  Logs one
    line saying how long the worker took to go and whether it needed the
    SIGKILL: the waits above are set from those lines."""
    t0 = time.monotonic()
    gone = wait_pid_exit(pid, _EXIT_WAIT_S)
    sigkill = not gone
    if sigkill:
        try:
            os.kill(pid, signal.SIGKILL)
            gone = wait_pid_exit(pid, _KILL_WAIT_S)
        except ProcessLookupError:
            gone = True
    logger.log(
        logging.WARNING if sigkill else logging.INFO,  # a process without a logging set-up still shows the SIGKILL case
        "%s",
        json.dumps(
            {
                "event": "tpu_worker_reaped",
                "pid": pid,
                "chips": detect_chips(),
                "seconds": round(time.monotonic() - t0, 3),
                "sigkill": sigkill,
                "gone": gone,
            }
        ),
    )
    if gone:
        return None
    return (
        f"TPU worker pid {pid} is still alive {REAP_WAIT_S:.0f}s "
        f"after SIGTERM and SIGKILL; the host's chips stay taken until it exits"
    )
