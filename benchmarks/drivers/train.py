"""A training cell: ``JaxTrainer`` -> TPU worker actor -> ``make_train_step``
over the configuration's mesh, fed by the traffic mix's input pipeline.

``run`` is called in the driver process and touches no JAX backend;
``train_loop`` runs inside the TPU worker."""

from __future__ import annotations

import importlib
import os
from typing import Mapping


def train_loop(config: Mapping) -> None:
    """``train_loop_per_worker``.  Set-up (weights from the seed, reference
    check, compile, warm-up), then the measured window, then one report."""
    import collections
    import time

    import jax
    import jax.numpy as jnp

    from ray_tpu.air import session
    from ray_tpu.models.gpt2 import GPT2Config, GPT2Model
    from ray_tpu.models.lm_train import make_train_step
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg, traffic, seed = config["config"], config["traffic"], int(config["seed"])
    tr = cfg["train"]
    chips = int(cfg["chips"])
    devices = jax.devices()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(name) if "backend_compile" in name else None
    )

    gcfg = GPT2Config(
        vocab_size=cfg["vocab_size"], n_layer=cfg["n_layer"], n_head=cfg["n_head"], n_embd=cfg["n_embd"],
        block_size=cfg["n_positions"], compute_dtype=jnp.dtype(tr["compute_dtype"]), param_dtype=jnp.dtype(tr["param_dtype"]),
    )
    model = GPT2Model(gcfg)
    layout = {k: int(v) for k, v in cfg["layout"].items()}
    mesh = make_mesh(MeshConfig(**layout), jax.local_devices()[:chips])
    bundle = make_train_step(
        model, mesh, learning_rate=tr["learning_rate"], weight_decay=tr["weight_decay"], grad_clip=tr["grad_clip"]
    )
    # weights on the device, in one jitted call, from the seed
    params, opt_state = bundle.init(jax.random.PRNGKey(seed % (2**31)))

    batch = int(tr["per_chip_batch"]) * chips
    seq = int(tr["seq"])
    loadgen = importlib.import_module(f"benchmarks.loadgen.{traffic['kind']}")
    batches = loadgen.make(traffic, seed, batch, cfg["vocab_size"])

    # ---- correctness, before the window: the system against the plain reference
    check = reference_check(model, params, mesh, cfg, next(batches), max(2, chips))

    def put(host):
        return jax.device_put(host[0], bundle.batch_sharding), jax.device_put(host[1], bundle.batch_sharding)

    tokens, targets = put(next(batches))
    t_c = time.perf_counter()
    compiled = bundle.step.lower(params, opt_state, tokens, targets).compile()
    compile_s = time.perf_counter() - t_c
    ma = compiled.memory_analysis()
    analysis = {
        k: int(getattr(ma, k, 0) or 0)
        for k in ("argument_size_in_bytes", "output_size_in_bytes", "alias_size_in_bytes", "temp_size_in_bytes", "generated_code_size_in_bytes")
    }
    hlo = compiled.as_text()
    warm_losses = []
    for _ in range(int(traffic.get("warmup_steps", 3))):
        params, opt_state, m = compiled(params, opt_state, *put(next(batches)))
        warm_losses.append(m["loss"])
    jax.block_until_ready(m)
    warm_losses = [float(x) for x in warm_losses]

    # ---- the measured window
    Ann = jax.profiler.TraceAnnotation
    depth = int(traffic.get("pipeline_depth", 4))
    seconds = float(config["seconds"])
    trace_dir = config.get("trace_dir")
    trace_at, trace_len = seconds / 3.0, float(traffic.get("trace_seconds", 3.0))
    tracing, traced = False, False
    pending = collections.deque()
    losses = []
    wait_s, steps, trace_call_s = 0.0, 0, 0.0
    compiles_before = len(compiles)
    if config.get("window_mark"):
        open(config["window_mark"], "w").close()  # run.py: from here on a failure is never retried
    t_epoch = time.time()
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if trace_dir and not traced and not tracing and now >= trace_at:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing, t_trace = True, time.perf_counter() - t0
            trace_call_s += t_trace - now
        if tracing and now >= t_trace + trace_len:
            # no drain first: with 2.9 s steps the four queued ones made a 17.5 s
            # trace of four devices, and writing it took minutes (PERF.md section 7)
            jax.profiler.stop_trace()  # writes the file: seconds, none of them the program's
            tracing, traced = False, True
            trace_call_s += time.perf_counter() - t0 - now
        a = time.perf_counter()
        with Ann("bench/data_wait"):
            host = next(batches)
        with Ann("bench/h2d"):
            tok, tgt = put(host)
        wait_s += time.perf_counter() - a
        with Ann("bench/dispatch"):
            params, opt_state, m = compiled(params, opt_state, tok, tgt)
        pending.append(m)
        steps += 1
        if len(pending) > depth:
            with Ann("bench/block_on_step_minus_depth"):
                old = pending.popleft()
                losses.append(float(old["loss"]))
    with Ann("bench/block_final"):
        jax.block_until_ready(m)
    t1 = time.perf_counter() - trace_call_s
    if tracing:
        jax.profiler.stop_trace()
    for old in pending:
        losses.append(float(old["loss"]))
    batches.close()

    peak_stats = 0
    for d in jax.local_devices():
        peak_stats = max(peak_stats, int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)))
    session.report(
        {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "window_epoch": t_epoch,
            "window_s": t1 - t0,
            "steps": steps,
            "tokens_per_step": batch * seq,
            "data_wait_s": wait_s,
            "trace_call_s": trace_call_s,
            "losses": losses,
            "warm_losses": warm_losses,
            "compile_s": compile_s,
            "compiles_in_window": len(compiles) - compiles_before,
            "compiles_total": len(compiles),
            "check": check,
            "memory_analysis": analysis,
            "peak_bytes_in_use": peak_stats,
            "pallas_calls": hlo.count("tpu_custom_call"),
            "mesh": {k: int(v) for k, v in mesh.shape.items()},
            "cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        }
    )


def reference_check(model, params, mesh, cfg: Mapping, host_batch, n_seq: int, *, cast=None) -> dict:
    """Loss and global gradient norm of the system (its own loss function:
    bf16 compute, the attention kernel, remat) against the plain float32
    reference, on the first ``n_seq`` sequences of a seeded batch at the
    initial weights.  ``cast`` (a dtype) rounds the weights first: used once,
    by hand, to show that the tolerance would catch bf16 parameters."""
    import jax
    import jax.numpy as jnp
    import optax

    from benchmarks.reference import gpt2_ref

    tokens, targets = host_batch[0][:n_seq], host_batch[1][:n_seq]
    sys_params = params if cast is None else jax.tree.map(lambda a: a.astype(cast).astype(a.dtype), params)
    mode = cfg["train"].get("reference_check", "loss_and_grad_norm")

    if mode == "loss":
        sys_loss = jax.jit(lambda p, t, g: model.loss(p, t, g, mesh))(sys_params, tokens, targets)
        with jax.default_matmul_precision("highest"):
            ref_loss = jax.jit(
                lambda p, t, g: gpt2_ref.loss(p, t, g, n_head=cfg["n_head"], vocab_size=cfg["vocab_size"], eps=cfg["layer_norm_epsilon"])
            )(params, tokens, targets)
        sys_gn = ref_gn = None
    else:
        def sys_fn(p, t, g):
            val, grads = jax.value_and_grad(lambda p_: model.loss(p_, t, g, mesh))(p)
            return val, optax.global_norm(grads)

        sys_loss, sys_gn = jax.jit(sys_fn)(sys_params, tokens, targets)
        ref_loss, ref_gn = jax.jit(
            lambda p, t, g: gpt2_ref.loss_and_grad_norm(
                p, t, g, n_head=cfg["n_head"], vocab_size=cfg["vocab_size"], eps=cfg["layer_norm_epsilon"]
            )
        )(params, tokens, targets)
    out = {"mode": mode, "n_seq": int(n_seq), "sys_loss": float(sys_loss), "ref_loss": float(ref_loss)}
    out["loss_rel_err"] = abs(out["sys_loss"] - out["ref_loss"]) / abs(out["ref_loss"])
    out["loss_tol"] = LOSS_REL_TOL
    ok = out["loss_rel_err"] <= LOSS_REL_TOL
    if sys_gn is not None:
        out.update(sys_grad_norm=float(sys_gn), ref_grad_norm=float(ref_gn))
        out["grad_norm_rel_err"] = abs(out["sys_grad_norm"] - out["ref_grad_norm"]) / out["ref_grad_norm"]
        out["grad_norm_tol"] = GRAD_NORM_REL_TOL
        ok = ok and out["grad_norm_rel_err"] <= GRAD_NORM_REL_TOL
    out["ok"] = bool(ok)
    return out


# Tolerances of the training comparison, with their reasons.  The system
# computes in bf16 with float32 accumulation, parameters, softmax and loss.
# Over 14 seeded batches on the v5e at the published sizes its loss was
# within 3.3e-5 (relative) of the float32 reference and its global gradient
# norm within 4.0e-4 (my chip runs, PR 23); the bounds are five times that.
# What rounding the parameters to bf16 does to both is in PERF.md section 6.
LOSS_REL_TOL = 2e-4
GRAD_NORM_REL_TOL = 2e-3


def run(ctx) -> dict:
    """Driver side: start the trainer, wait for its one report, judge it."""
    import math

    from ray_tpu.train import JaxTrainer, ScalingConfig

    cfg = ctx.config
    chips = int(cfg["chips"])
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={
            "config": cfg,
            "traffic": ctx.traffic,
            "seed": ctx.seed,
            "seconds": ctx.seconds,
            "trace_dir": ctx.trace_dir if ctx.trace else None,
            "window_mark": ctx.window_mark,
        },
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True, tpu_chips_per_worker=chips),
    )
    r = trainer.fit().metrics
    losses = r["losses"]
    problems = []
    if not r["check"]["ok"]:
        problems.append(f"reference check failed: {r['check']}")
    if not all(math.isfinite(x) for x in losses + r["warm_losses"]):
        problems.append("non-finite loss")
    if len(losses) >= 16 and not (sum(losses[-8:]) / 8 < sum(losses[:8]) / 8):
        problems.append(f"loss did not fall: first 8 {losses[:8]}, last 8 {losses[-8:]}")
    if r["compiles_in_window"]:
        problems.append(f"{r['compiles_in_window']} compilation(s) inside the window")
    if r["device_count"] != chips:
        problems.append(f"the worker sees {r['device_count']} devices, the cell asks for {chips}")
    if r["platform"] == "tpu" and not r["pallas_calls"]:
        problems.append("the compiled train step holds no tpu_custom_call: attention is not the splash kernel")
    ma = r["memory_analysis"]
    program_peak = ma["argument_size_in_bytes"] + ma["output_size_in_bytes"] - ma["alias_size_in_bytes"] + ma["temp_size_in_bytes"]
    window = r["window_s"]
    tok_s_chip = r["steps"] * r["tokens_per_step"] / window / chips
    return {
        "correct": not problems,
        "problems": problems,
        "attempted": r["steps"],
        "failed": sum(1 for x in losses if not math.isfinite(x)),
        "window_epoch": r["window_epoch"],
        "e2e": {"train_tokens_per_s_per_chip": (tok_s_chip, "tokens/s/chip")},
        "device": {
            "platform": r["platform"],
            "kind": r["device_kind"],
            "count": r["device_count"],
            # the backend's peak_bytes_in_use leaves out a program's temporaries
            # (PERF.md section 7), so the larger of it and XLA's own account of
            # the step (arguments + outputs - aliased + temporaries) is reported
            "memory_peak_bytes": max(r["peak_bytes_in_use"], program_peak),
        },
        "counters": {
            "window_s": window,
            "steps": r["steps"],
            "tokens_per_step": r["tokens_per_step"],
            "tokens_per_s_per_chip": tok_s_chip,
            "data_wait_s": r["data_wait_s"],
            "chips": chips,
            "seq": int(cfg["train"]["seq"]),
            "batch_per_chip": int(cfg["train"]["per_chip_batch"]),
        },
        "notes": {k: r[k] for k in ("check", "compile_s", "memory_analysis", "peak_bytes_in_use", "mesh", "pallas_calls", "compiles_total", "cache_dir")}
        | {"first_losses": losses[:4], "last_losses": losses[-4:], "warm_losses": r["warm_losses"]},
        "host_thread": None,
    }
