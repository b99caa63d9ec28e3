"""Does the system still start on the chip?  One command, one exit code.

    python3 chip_smoke.py

drives the two product paths once each, through the entry points a user
calls, at the full width of a model the repo supports, with seeded random
weights, on every chip of this host (N = 1 on a one-chip machine, 4 on a
2x2 host):

- train: ``JaxTrainer`` -> TPU worker actor -> ``make_train_step`` over a
  ``dp=N`` mesh, GPT-2 124M at sequence 1024, bf16, batch 18 per chip;
- serve: ``serve.run(engine_llm_deployment(...))`` -> the continuous-batching
  engine, llama_3b in bf16 sharded ``tp=N``, answering requests of mixed
  prompt lengths, several in flight, one streamed.

It checks what comes back (platform, shapes, finite and falling loss, the
Pallas kernel in the compiled step, shard placement, token counts, the
streamed and the buffered answer to one prompt agreeing), prints what it found
as one ``summary: {...}`` line and, as the last line of its standard output,

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with exactly these keys, the device as JAX reported it inside the TPU worker.
It exits non-zero, printing no result, when the host exposes no TPU chip, when
the ``ray_tpu`` package is not beside it, when any check fails, and when it
runs out of time.
Nothing here falls back to the CPU, and nothing lets a failed phase exit 0.

The driver process never initialises a JAX backend: the chips belong to the
one TPU worker (ray_tpu/_private/tpu.py).  It writes the compile cache
(``JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``) and, on a
failure, the session's logs under ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import glob
import json
import math
import os
import shutil
import signal
import sys
import threading
import time
import traceback

# the contract allows 1200 s, compilation included; leave room to tear down
_DEADLINE_S = 1100
_HERE = os.path.dirname(os.path.abspath(__file__))
_OUT_DIR = os.path.join(_HERE, "chiprun_out", "chip_smoke")


class SmokeFailure(Exception):
    """A check on what a phase returned did not hold."""


def _require(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


# --------------------------------------------------------------------- train


def train_loop(config):
    """``train_loop_per_worker``: runs inside the TPU worker actor."""
    import os
    import time

    import jax

    from ray_tpu.air import session
    from ray_tpu.models.gpt2 import GPT2Config, GPT2Model
    from ray_tpu.models.lm_train import make_train_step, synthetic_batch
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh

    chips = config["chips"]
    devices = jax.devices()
    cfg = getattr(GPT2Config, config["model"])()
    seq = cfg.block_size
    mesh = make_mesh(MeshConfig(dp=chips), jax.local_devices())
    bundle = make_train_step(GPT2Model(cfg), mesh, learning_rate=3e-4)
    params, opt_state = bundle.init(jax.random.PRNGKey(0))
    # one seeded batch, reused: a falling loss then proves the update
    tokens, targets = synthetic_batch(
        jax.random.PRNGKey(1), config["per_chip_batch"] * chips, seq, cfg.vocab_size
    )
    tokens = jax.device_put(tokens, bundle.batch_sharding)
    targets = jax.device_put(targets, bundle.batch_sharding)

    lowered = bundle.step.lower(params, opt_state, tokens, targets)
    stablehlo = lowered.as_text()
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()

    losses = []
    for _ in range(config["warmup"]):
        params, opt_state, metrics = compiled(params, opt_state, tokens, targets)
        losses.append(metrics["loss"])
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(config["steps"]):
        params, opt_state, metrics = compiled(params, opt_state, tokens, targets)
        losses.append(metrics["loss"])
    jax.block_until_ready(metrics)
    step_ms = (time.perf_counter() - t0) / config["steps"] * 1e3

    stats = devices[0].memory_stats() or {}
    session.report(
        {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "local_device_count": len(jax.local_devices()),
            "model": config["model"],
            "seq": seq,
            "vocab": cfg.vocab_size,
            "per_chip_batch": config["per_chip_batch"],
            "mesh": {k: v for k, v in mesh.shape.items() if v > 1},
            "batch_shards": sorted(
                (s.device.id, s.data.shape[0]) for s in tokens.addressable_shards
            ),
            "steps": config["steps"],
            "losses": [float(x) for x in losses],
            "compile_s": round(compile_s, 2),
            "step_ms": round(step_ms, 2),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            # the splash kernel is the only Pallas kernel in the tree; where
            # the step holds none, attention is the einsum composition
            "pallas_calls": stablehlo.count("tpu_custom_call"),
            "all_reduces": hlo.count("all-reduce"),
            "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        }
    )


def run_train(chips: int, model: str, per_chip_batch: int, warmup: int, steps: int) -> dict:
    from ray_tpu.train import JaxTrainer, ScalingConfig

    trainer = JaxTrainer(
        train_loop,
        train_loop_config={
            "chips": chips,
            "model": model,
            "per_chip_batch": per_chip_batch,
            "warmup": warmup,
            "steps": steps,
        },
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=True, tpu_chips_per_worker=chips
        ),
    )
    # fit() returns after the worker group is shut down, and that returns
    # after the TPU worker's process is gone: the chips are free for serve
    return trainer.fit().metrics


def check_train(r: dict, chips: int, cache_dir: str):
    _require(r["platform"] == "tpu", f"train ran on platform {r['platform']!r}, not tpu")
    _require(
        r["device_count"] == r["local_device_count"] == chips,
        f"the head counted {chips} chips, the TPU worker sees "
        f"{r['device_count']} devices ({r['local_device_count']} local)",
    )
    _require(r["mesh"] == ({"dp": chips} if chips > 1 else {}), f"mesh is {r['mesh']}")
    _require(
        len(r["batch_shards"]) == chips
        and len({d for d, _ in r["batch_shards"]}) == chips
        and all(rows == r["per_chip_batch"] for _, rows in r["batch_shards"]),
        f"batch shards (device, rows) {r['batch_shards']}: want {r['per_chip_batch']} "
        f"rows on each of {chips} distinct devices",
    )
    losses = r["losses"]
    _require(all(math.isfinite(x) for x in losses), f"non-finite loss in {losses}")
    # reference for a small input: seeded N(0, 0.02) weights put near-uniform
    # mass on the vocabulary, so the first loss is ln(vocab) within a few %
    _require(
        abs(losses[0] - math.log(r["vocab"])) < 0.5,
        f"first loss {losses[0]:.3f} is not near ln({r['vocab']}) = {math.log(r['vocab']):.3f}",
    )
    _require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    _require(
        r["pallas_calls"] > 0,
        "the compiled train step holds no tpu_custom_call: attention is the "
        "einsum reference, not the splash kernel",
    )
    _require(
        chips == 1 or r["all_reduces"] > 0,
        f"dp={chips} train step compiled without an all-reduce",
    )
    _require(bool(r["peak_bytes_in_use"]), "the device reported no peak_bytes_in_use")
    _require(r["cache_dir"] == cache_dir, f"TPU worker caches in {r['cache_dir']}, driver in {cache_dir}")


# --------------------------------------------------------------------- serve


def run_serve(chips: int, model: str, prompt_lens, new_tokens: int) -> dict:
    """Deploy the engine, answer ``len(prompt_lens) + 1`` requests: the first
    alone (it compiles), the rest together, plus the first one's prompt again
    through ``stream_tokens`` while they are in flight."""
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import engine_llm_deployment

    dep = engine_llm_deployment(
        model,
        max_seq_len=max(prompt_lens) + new_tokens,
        new_tokens=new_tokens,
        num_slots=8,
        num_tpus=chips,
        tp=chips,
    )
    t0 = time.perf_counter()
    handle = serve.run(dep.bind())
    # answered once the replica's constructor has run: weights on the chips
    ray_tpu.get(handle.method("info").remote(), timeout=900)
    ready_s = time.perf_counter() - t0

    rng = np.random.default_rng(0)
    prompts = [[int(t) for t in rng.integers(1, 255, n)] for n in prompt_lens]
    t0 = time.perf_counter()
    first = ray_tpu.get(handle.remote(prompts[0]), timeout=900)
    compile_s = time.perf_counter() - t0  # prefill + decode programs, then new_tokens steps

    streamed: list = []

    def _stream():
        for frame in handle.stream_tokens(prompts[0]):
            streamed.extend(frame)

    t0 = time.perf_counter()
    refs = [handle.remote(p) for p in prompts[1:]]
    th = threading.Thread(target=_stream, daemon=True)
    th.start()
    answers = [first] + ray_tpu.get(refs, timeout=600)
    th.join(600)
    if th.is_alive():
        raise SmokeFailure("stream_tokens did not finish within 600 s")
    batch_s = time.perf_counter() - t0
    info = ray_tpu.get(handle.method("info").remote(), timeout=60)
    t0 = time.perf_counter()
    serve.delete(dep.name)  # returns once the replica's process is gone
    delete_s = time.perf_counter() - t0
    serve.shutdown()
    return {
        "platform": info["platform"],
        "model": model,
        "params_b": info["params_b"],
        "tp": info["tp"],
        "shards": info["shards"],
        "engine": {k: info["engine"][k] for k in ("compile_prefill", "compile_decode", "tokens_generated")},
        "requests_sent": len(prompts) + 1,
        "answers": answers,
        "streamed": streamed,
        "new_tokens": new_tokens,
        "ready_s": round(ready_s, 2),
        "compile_s": round(compile_s, 2),
        "batch_s": round(batch_s, 2),
        "delete_s": round(delete_s, 2),
    }


def check_serve(r: dict, chips: int):
    _require(r["platform"] == "tpu", f"replica runs on platform {r['platform']!r}, not tpu")
    _require(r["tp"] == chips, f"replica shards tp={r['tp']}, granted {chips} chips")
    per_dev = r["shards"]["per_device_bytes"]
    want = r["shards"]["total_bytes"] / chips
    _require(
        len(per_dev) == chips and all(abs(b - want) <= 0.02 * want for b in per_dev.values()),
        f"parameter bytes per device {per_dev}: want {want:.3g} on each of {chips}",
    )
    answers = r["answers"] + [r["streamed"]]
    _require(len(answers) == r["requests_sent"], "a request went unanswered")
    _require(
        all(len(a) == r["new_tokens"] for a in answers),
        f"token counts {[len(a) for a in answers]}: want {r['new_tokens']} each",
    )
    # greedy decoding: one prompt, two routes (buffered alone, streamed in a
    # full fleet) through one compiled program give one answer
    _require(r["streamed"] == r["answers"][0], "streamed and buffered answers differ")
    _require(
        r["engine"]["compile_prefill"] == r["engine"]["compile_decode"] == 1,
        f"engine programs recompiled: {r['engine']}",
    )


# ------------------------------------------------------------------- result


def summarize(head_tpu, cache_dir: str, train: dict, serve: dict) -> dict:
    """What the run found, from the two checked reports: the ``summary:``
    line.  ``device`` is as JAX reported it from inside the TPU worker."""
    losses = train["losses"]
    return {
        "ok": True,
        "device": {
            "platform": train["platform"],
            "kind": train["device_kind"],
            "count": train["device_count"],
        },
        "head_tpu": head_tpu,
        "cache_dir": cache_dir,
        "train": {
            "model": train["model"],
            "seq": train["seq"],
            "per_chip_batch": train["per_chip_batch"],
            "mesh": train["mesh"],
            "steps": train["steps"],
            "first_loss": round(losses[0], 4),
            "last_loss": round(losses[-1], 4),
            "compile_s": train["compile_s"],
            "step_ms": train["step_ms"],
            "peak_bytes_in_use": train["peak_bytes_in_use"],
            "attention": "splash" if train["pallas_calls"] else "xla",
            "all_reduces": train["all_reduces"],
        },
        "serve": {
            "model": serve["model"],
            "tp": serve["tp"],
            "requests_sent": serve["requests_sent"],
            "requests_answered": len(serve["answers"]) + bool(serve["streamed"]),
            "tokens_returned": sum(map(len, serve["answers"])) + len(serve["streamed"]),
            "ready_s": serve["ready_s"],
            "compile_s": serve["compile_s"],
            "delete_s": serve["delete_s"],
            "per_device_param_bytes": sorted(serve["shards"]["per_device_bytes"].values()),
        },
    }


def result_line(summary: dict) -> str:
    """The last line of stdout: these keys and no other."""
    return json.dumps({"ok": summary["ok"], "device": summary["device"]})


# ---------------------------------------------------------------------- main


def _dump_logs(session_dir: str):
    """A libtpu abort shows in the driver only as a timeout or a dead actor:
    print the end of the newest worker logs, keep all of them."""
    if not session_dir or not os.path.isdir(session_dir):
        return
    os.makedirs(_OUT_DIR, exist_ok=True)
    logs = sorted(glob.glob(os.path.join(session_dir, "*.log")), key=os.path.getmtime)
    for path in logs:
        shutil.copy(path, _OUT_DIR)
    workers = [p for p in logs if os.path.basename(p).startswith("worker-")]
    for path in [os.path.join(session_dir, "head.log")] + workers[-3:]:
        try:
            with open(path, errors="replace") as f:
                tail = f.read()[-4000:]
        except OSError:
            continue
        print(f"----- tail of {path}\n{tail}", file=sys.stderr)


def _on_deadline(signum, frame):
    raise TimeoutError(f"chip_smoke.py ran past {_DEADLINE_S} s")


def main() -> int:
    try:
        from ray_tpu._private import tpu
    except ImportError as e:
        print(f"chip_smoke.py: {e}: run it from the root of a checkout", file=sys.stderr)
        return 2

    chips = tpu.detect_chips()
    if chips == 0:
        print(
            "chip_smoke.py: this host exposes no TPU chip (no /dev/accel*, no "
            "/dev/vfio/<n>); nothing was run",
            file=sys.stderr,
        )
        return 2
    # the cluster's processes inherit this environment: the TPU worker must
    # get the chip or fail, whatever the calling shell said
    os.environ["JAX_PLATFORMS"] = "tpu"
    cache_dir = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", tpu.compile_cache_dir())
    # ... and this process must never take it
    import jax

    jax.config.update("jax_platforms", "cpu")

    import ray_tpu
    from ray_tpu._private.worker import global_worker

    signal.signal(signal.SIGALRM, _on_deadline)
    signal.alarm(_DEADLINE_S)
    session_dir = ""
    try:
        ray_tpu.init()
        session_dir = global_worker.session_dir
        head_tpu = ray_tpu.cluster_resources().get("TPU", 0)
        _require(head_tpu == chips, f"head registered TPU: {head_tpu}, host exposes {chips}")

        train = run_train(chips, model="gpt2_124m", per_chip_batch=18, warmup=2, steps=5)
        print("train:", json.dumps(train), flush=True)
        check_train(train, chips, cache_dir)

        serve = run_serve(
            chips, model="llama_3b", prompt_lens=[16, 200, 40, 120, 64, 24, 160, 90], new_tokens=32
        )
        print(
            "serve:",
            json.dumps({k: v for k, v in serve.items() if k not in ("answers", "streamed")}),
            flush=True,
        )
        check_serve(serve, chips)

        from jax._src import xla_bridge

        _require(
            not xla_bridge.backends_are_initialized(),
            "the driver process initialised a JAX backend",
        )
    except BaseException:
        traceback.print_exc()
        _dump_logs(session_dir)
        return 1
    finally:
        signal.alarm(0)
        ray_tpu.shutdown()

    summary = summarize(head_tpu, cache_dir, train, serve)
    print("summary:", json.dumps(summary), flush=True)
    print(result_line(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
