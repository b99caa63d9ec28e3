"""Headline benchmark: GPT-2 124M training throughput on the real TPU,
measured THROUGH the product path: JaxTrainer → BackendExecutor → a
TPU-claiming worker actor running the train loop (the Ray-Train-style
GPT-2 of BASELINE.json; reference analog:
release/air_tests/air_benchmarks/workloads/torch_benchmark.py:214-222).

Prints ONE JSON line:
  {"metric": "gpt2_124m_tokens_per_sec_per_chip", "value": N,
   "unit": "tokens/s/chip", "vs_baseline": MFU/0.45, ...}

vs_baseline is measured MFU against the north-star 45% MFU target from
BASELINE.json (the reference repo publishes no absolute numbers —
BASELINE.md).

The driver pins its own jax to CPU: the chips belong to the one TPU worker
the cluster spawns (ray_tpu/_private/tpu.py).  It fails where there is no
chip, where the device is not in the peaks table, and where any phase
fails — it never reruns on the CPU.  BENCH_PATH=raw runs the step loop
directly in this process instead (no cluster) for path-overhead comparison.
"""

from __future__ import annotations

import json
import os
import sys
import time

def _bench_config():
    return {
        "model": os.environ.get("BENCH_MODEL", "gpt2_124m"),
        # batch 18 is the sweet spot on a 16G v5e: largest batch whose
        # [B,S,V] f32 logits still fit the naive-CE budget (no backward
        # recompute); 30 steps measures steady state past warmup jitter
        "batch": int(os.environ.get("BENCH_BATCH", "18")),
        "steps": int(os.environ.get("BENCH_STEPS", "30")),
        "remat": os.environ.get("BENCH_REMAT", ""),
        "attn": os.environ.get("BENCH_ATTN", ""),
        "scores": os.environ.get("BENCH_SCORES", "bf16"),
        "ce_chunk": os.environ.get("BENCH_CE_CHUNK", ""),
    }


def _build_bundle(cfg_d):
    """Model + jitted train step on THIS process's devices (runs inside the
    TPU worker on the train path; in-process on the raw path)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt2 import GPT2Config, GPT2Model
    from ray_tpu.models.lm_train import make_train_step, synthetic_batch
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg_kw = {}
    if cfg_d["remat"]:
        cfg_kw["remat_policy"] = cfg_d["remat"]
        cfg_kw["remat"] = cfg_d["remat"] != "none"
    if cfg_d["attn"]:
        cfg_kw["attention_impl"] = cfg_d["attn"]
    if cfg_d["scores"] == "bf16":
        # bf16 attention scores halve [S,S] HBM traffic on the xla path
        cfg_kw["attn_scores_dtype"] = jnp.bfloat16
    if cfg_d["ce_chunk"]:
        cfg_kw["loss_chunk"] = int(cfg_d["ce_chunk"])
    cfg = getattr(GPT2Config, cfg_d["model"])(**cfg_kw)
    model = GPT2Model(cfg)
    devices = jax.devices()
    mesh = make_mesh(MeshConfig(dp=1), devices[:1])
    bundle = make_train_step(model, mesh, learning_rate=3e-4)
    return cfg, bundle, devices


def _run_steps(cfg_d):
    """The measured loop; returns a metrics dict.  Called inside whichever
    process owns the chip."""
    import jax

    from ray_tpu.models.lm_train import synthetic_batch

    cfg, bundle, devices = _build_bundle(cfg_d)
    batch, steps = cfg_d["batch"], cfg_d["steps"]
    seq = cfg.block_size

    params, opt_state = bundle.init(jax.random.PRNGKey(0))
    tokens, targets = synthetic_batch(jax.random.PRNGKey(1), batch, seq, cfg.vocab_size)
    tokens = jax.device_put(tokens, bundle.batch_sharding)
    targets = jax.device_put(targets, bundle.batch_sharding)

    for _ in range(2):  # warmup (compile)
        params, opt_state, metrics = bundle.step(params, opt_state, tokens, targets)
    jax.block_until_ready(metrics)

    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, metrics = bundle.step(params, opt_state, tokens, targets)
    jax.block_until_ready(metrics)  # the whole step chain
    dt = time.perf_counter() - t0
    final_loss = float(metrics["loss"])

    # probed pass AFTER the timed loop: per-step breakdown + jitter via
    # the flight recorder's StepProbe (train/jax/step_probe.py) without
    # perturbing the headline async-dispatch throughput above (the probe
    # brackets compute with a sync point by design)
    probe_steps = max(4, steps // 4)
    from ray_tpu.train.jax import StepProbe

    probe = StepProbe(
        "bench_gpt2",
        flops_per_step=cfg.flops_per_token() * batch * seq,
    )
    for _ in range(probe_steps):
        with probe.step():
            with probe.phase("compute"):
                params, opt_state, metrics = bundle.step(
                    params, opt_state, tokens, targets
                )
                probe.block(metrics)
            with probe.phase("metrics_fold"):
                float(metrics["loss"])
    probe.flush()
    st = probe.stats()
    jitter = {
        "probed_step_ms_p50": round(st.get("p50_s", 0) * 1e3, 2),
        "probed_step_ms_p99": round(st.get("p99_s", 0) * 1e3, 2),
        "step_jitter_pct": round(st.get("jitter_pct", 0), 2),
    }

    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "tokens_per_sec": batch * seq * steps / dt,
        "flops_per_token": cfg.flops_per_token(),
        "step_ms": 1000 * dt / steps,
        "seq": seq,
        "loss": final_loss,
        **jitter,
    }


def _train_loop(config):
    """Runs on the TPU worker actor via JaxTrainer.  config carries the
    primary model config and optionally a "secondary" config benched in
    the same worker process (one process holds the chips)."""
    from ray_tpu.air import session

    secondary = config.pop("secondary", None)
    out = _run_steps(config)
    if secondary is not None:
        out["secondary"] = _run_steps(secondary)
    session.report(out)


def _dispatch_pair():
    """Per-step driver-overhead pair (ROADMAP item 2): the SAME tiny LM
    ``TrainStepSpec`` driven through the eager per-step actor-call path vs
    the gang-armed resident DAG loop (train/jax/step_dag.py), through the
    real cluster.  Identical stage functions, identical model/config — the
    per-step wall-clock gap is the driver dispatch cost the resident DAG
    deletes.  Runs LAST (the headline fit has released the chip) and pins
    the pair to CPU: dispatch is a host-path property, and the pair must
    never re-claim the chip."""
    import ray_tpu
    from ray_tpu.models.lm_train import make_lm_step_spec
    from ray_tpu.train._internal.worker_group import TrainWorker
    from ray_tpu.train.jax.step_dag import TrainStepDag, _EagerSpecDriver

    os.environ["JAX_PLATFORMS"] = "cpu"
    steps = int(os.environ.get("BENCH_DISPATCH_STEPS", "60"))
    ray_tpu.init(num_cpus=4)
    try:
        spec = make_lm_step_spec(
            "tiny",
            batch=2,
            seq=64,
            steps=1 << 30,  # driven by the timers below, not the spec
            sync_grads=False,
            name="bench_dispatch",
        )
        tw = ray_tpu.remote(TrainWorker).remote(0, 1)
        eager = _EagerSpecDriver([tw], spec, None, 0)
        eager.run(5)  # build + jit warmup off the clock
        t0 = time.perf_counter()
        eager.run(steps)
        eager_ms = (time.perf_counter() - t0) / steps * 1e3
        eager.finish()
        dag = TrainStepDag([tw], spec)  # rebuilds state; same seed
        dag.run(5)
        t0 = time.perf_counter()
        dag.run(steps)
        dag_ms = (time.perf_counter() - t0) / steps * 1e3
        dag.teardown()
        return {
            "eager_step_ms": round(eager_ms, 3),
            "dag_step_ms": round(dag_ms, 3),
            "driver_overhead_ms": round(eager_ms - dag_ms, 3),
            "dispatch_speedup": round(eager_ms / dag_ms, 2),
            "model": "tiny",
            "steps": steps,
        }
    finally:
        ray_tpu.shutdown()


def main():
    from ray_tpu._private import tpu
    from ray_tpu.train.jax.step_probe import peak_flops_per_device

    if not tpu.detect_chips():
        sys.exit("bench.py: this host exposes no TPU chip; it measures nothing on a CPU")
    cfg_d = _bench_config()
    raw = os.environ.get("BENCH_PATH", "train") == "raw"

    cfg2 = None
    if os.environ.get("BENCH_SECONDARY", "1") != "0":
        # secondary row: gpt2_350m on the same chip (BASELINE config #4
        # evidence ladder — the 1.5B shape itself is validated by the
        # dryrun's ZeRO-1 shard assertions)
        cfg2 = dict(cfg_d)
        cfg2["model"] = "gpt2_350m"
        cfg2["batch"] = int(os.environ.get("BENCH_BATCH_350M", "8"))
        cfg2["steps"] = 10

    if raw:
        m = _run_steps(cfg_d)
        m2 = _run_steps(cfg2) if cfg2 is not None else None
    else:
        # the chips belong to the TPU worker: pin this process's jax to CPU
        import jax

        jax.config.update("jax_platforms", "cpu")
        import ray_tpu
        from ray_tpu.train import JaxTrainer, ScalingConfig

        ray_tpu.init(num_cpus=4)
        try:
            trainer = JaxTrainer(
                _train_loop,
                train_loop_config={**cfg_d, "secondary": cfg2},
                scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
            )
            m = trainer.fit().metrics
        finally:
            ray_tpu.shutdown()
        m2 = m.pop("secondary", None)

    if m["platform"] != "tpu":
        raise RuntimeError(f"bench ran on {m['platform']!r}, not on the TPU")
    peak = peak_flops_per_device(m["device_kind"])
    if peak is None:
        raise RuntimeError(
            f"no peak FLOP/s known for device kind {m['device_kind']!r}: add it "
            f"to the table in ray_tpu/train/jax/step_probe.py with its source"
        )
    mfu = m["tokens_per_sec"] * m["flops_per_token"] / peak
    result = {
        "metric": "gpt2_124m_tokens_per_sec_per_chip",
        "value": round(m["tokens_per_sec"], 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "mfu": round(mfu, 4),
        "platform": m["platform"],
        "device_kind": m["device_kind"],
        "path": "raw" if raw else "train",
        "batch": cfg_d["batch"],
        "seq": m["seq"],
        "step_ms": round(m["step_ms"], 2),
        "loss": round(m["loss"], 4),
    }

    # step-dispatch pair: eager JaxTrainer loop vs the DAG-resident loop
    # on the same model/config — the tracked driver-overhead line
    # (scripts/perf_trends.py series bench.train_dispatch_*)
    if not raw and os.environ.get("BENCH_DISPATCH", "1") != "0":
        result["step_dispatch"] = _dispatch_pair()

    if m2 is not None:
        mfu2 = m2["tokens_per_sec"] * m2["flops_per_token"] / peak
        result["gpt2_350m"] = {
            "tokens_per_sec_per_chip": round(m2["tokens_per_sec"], 1),
            "mfu": round(mfu2, 4),
            "batch": cfg2["batch"],
            "step_ms": round(m2["step_ms"], 2),
            "loss": round(m2["loss"], 4),
        }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
