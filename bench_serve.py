"""Serve LLM benchmark (BASELINE config #5 shape): Llama decode on the
TPU behind a @serve.batch deployment — tokens/s + request p50/p99 at
several offered loads, autoscaling engaged.

Product path: client → DeploymentHandle → TPU-claiming replica actor →
the tp-sharded ShardedLLM engine (ray_tpu/serve/llm.py, tp=1 on this
one-chip host; the SAME code path the multi-chip dryrun proves at
llama2_7b shape) — ONE jitted prefill+decode program per coalesced
batch with the KV cache donated.  Model: a llama-family config sized
for one 16G v5e chip in bf16 (llama2_7b bf16 weights alone are
~13.5 GB — 7B serving is the tp mesh story).  Reference analog:
python/ray/serve/benchmarks + serve/batching.py:46.

A second section (SERVE_BENCH_MIXED=1, default) replays one seeded
mixed-length Poisson trace against BOTH the static @serve.batch path and
the continuous-batching engine (ray_tpu/serve/engine/) and emits both
rows in the same JSON — per-class p50/p99, tokens/s, and the engine's
real TTFT/TPOT percentiles.  The legacy sweep stays untouched for
round-over-round comparability.

Writes SERVE_BENCH_r05.json and prints one JSON line.
"""

import json
import os
import time

import numpy as np

MAX_SEQ = 256
NEW_TOKENS = 32
MAX_BATCH = int(os.environ.get("SERVE_BENCH_MAX_BATCH", "8"))
MODEL = os.environ.get("SERVE_BENCH_MODEL", "llama_3b")

# ---- mixed-length Poisson workload (static vs continuous-batching engine)
# Short + long prompts interleaved at Poisson arrivals — the head-of-line
# blocking shape that saturated the static path in SERVE_BENCH_r04.  The
# tiny model keeps this section cheap on any backend (the comparison is
# about SCHEDULING, not FLOPs); set SERVE_BENCH_MIXED_MODEL to bench a
# real config, SERVE_BENCH_MIXED=0 to skip.
MIXED = os.environ.get("SERVE_BENCH_MIXED", "1") not in ("0", "false")
MIXED_MODEL = os.environ.get("SERVE_BENCH_MIXED_MODEL", "tiny")
MIXED_RPS = float(os.environ.get("SERVE_BENCH_MIXED_RPS", "72"))
MIXED_N = int(os.environ.get("SERVE_BENCH_MIXED_N", "240"))
# heterogeneous budgets are THE continuous-batching case: the static
# whole-request batch decodes EVERY member to the longest budget (its
# wire has one new_tokens), while the engine retires each sequence at
# its own — a short request stops at 8 tokens instead of riding out 48
MIXED_SHORT, MIXED_LONG = 4, 96  # prompt lengths
MIXED_NEW = {"short": 8, "long": 96}  # per-class token budgets
MIXED_LONG_FRAC = 0.25


# ---- fleet survival section (serve/FLEET.md): seeded Poisson stream
# spike against an SLO-autoscaled engine fleet — scale-out reaction
# time, mid-stream failover count under a replica kill, and client-side
# TTFT p99 with/without the kill.  Tiny model: the section measures the
# CONTROL plane (scaling, drain, failover), not FLOPs.
FLEET = os.environ.get("SERVE_BENCH_FLEET", "1") not in ("0", "false")
FLEET_N = int(os.environ.get("SERVE_BENCH_FLEET_N", "24"))
FLEET_RPS = float(os.environ.get("SERVE_BENCH_FLEET_RPS", "16"))
FLEET_NEW = int(os.environ.get("SERVE_BENCH_FLEET_NEW", "48"))


def _poisson_schedule(rng, n, rate):
    """Deterministic (seeded) arrival schedule replayed identically
    against both systems: [(t_offset, class, prompt_tokens)]."""
    t = 0.0
    sched = []
    for _ in range(n):
        t += rng.exponential(1.0 / rate)
        if rng.random() < MIXED_LONG_FRAC:
            cls, plen = "long", MIXED_LONG
        else:
            cls, plen = "short", MIXED_SHORT
        sched.append((t, cls, [int(x) for x in rng.integers(1, 255, plen)]))
    return sched


def _run_mixed(ray_tpu, handle, sched, per_request_budget: bool):
    """Replay the schedule open-loop (arrivals don't wait for
    completions — queueing shows up as latency, exactly like production
    traffic) and return per-class latency percentiles + useful-tokens/s.
    ``per_request_budget``: the engine honors a budget per request; the
    static path can't (one new_tokens per deployment) — that asymmetry
    is the system under test, not a bench artifact."""
    lat: dict = {}
    inflight: dict = {}

    def _reap(timeout):
        ready, _ = ray_tpu.wait(list(inflight), num_returns=1, timeout=timeout)
        for r in ready:
            t_sub, c = inflight.pop(r)
            ray_tpu.get(r, timeout=120)
            lat.setdefault(c, []).append(time.time() - t_sub)

    t0 = time.time()
    for t_off, cls, prompt in sched:
        while time.time() - t0 < t_off:
            if inflight:
                _reap(max(0.001, t_off - (time.time() - t0)))
            else:
                time.sleep(min(0.002, max(0.0, t_off - (time.time() - t0))))
        if per_request_budget:
            payload = {"prompt": prompt, "max_new_tokens": MIXED_NEW[cls]}
        else:
            payload = prompt
        inflight[handle.remote(payload)] = (time.time(), cls)
    while inflight:
        _reap(600)
    dt = time.time() - t0
    useful = sum(MIXED_NEW[cls] for _, cls, _ in sched)
    out = {"tokens_per_sec": round(useful / dt, 1)}
    for cls, vals in lat.items():
        ms = np.asarray(vals) * 1000
        out[cls] = {
            "n": len(vals),
            "p50_ms": round(float(np.percentile(ms, 50)), 1),
            "p99_ms": round(float(np.percentile(ms, 99)), 1),
        }
    return out


def mixed_workload_bench(ray_tpu, serve):
    """Static whole-request batching vs the continuous-batching engine on
    one seeded mixed-length Poisson trace; one JSON blob with both."""
    from ray_tpu.serve.llm import engine_llm_deployment, llm_deployment

    budget_max = max(MIXED_NEW.values())
    max_seq = MIXED_LONG + budget_max + 16
    sched = _poisson_schedule(np.random.default_rng(0), MIXED_N, MIXED_RPS)

    static = serve.run(
        llm_deployment(
            MIXED_MODEL, max_seq_len=max_seq, new_tokens=budget_max,
            max_batch_size=4, batch_wait_timeout_s=0.01, num_tpus=0, tp=1,
        ).options(name="llm_static_mixed").bind()
    )
    # warm every (batch size, padded prompt len) shape the trace can hit:
    # batches pad to their longest member, so P ∈ {short, long} only
    for plen in (MIXED_SHORT, MIXED_LONG):
        for b in range(1, 5):
            for _ in range(2):
                ray_tpu.get(
                    [static.remote([1] * plen) for _ in range(b)], timeout=1800
                )
    static_row = _run_mixed(ray_tpu, static, sched, per_request_budget=False)
    serve.delete("llm_static_mixed")

    engine = serve.run(
        engine_llm_deployment(
            MIXED_MODEL, max_seq_len=max_seq, new_tokens=budget_max,
            num_slots=8, page_size=16, prefill_chunk=16, num_tpus=0, tp=1,
        ).options(name="llm_engine_mixed").bind()
    )
    ray_tpu.get(engine.remote([1] * MIXED_SHORT), timeout=1800)  # warm
    engine_row = _run_mixed(ray_tpu, engine, sched, per_request_budget=True)

    # engine-side TTFT/TPOT are real per-request measurements from the
    # serve trace plane (first token host-visible at the prefill/decode
    # boundary)
    from ray_tpu.experimental.state import summarize_workloads

    s = summarize_workloads("serve")
    ttft = s.get("ttft", {}).get("llm_engine_mixed") or {}
    tpot = s.get("tpot", {}).get("llm_engine_mixed") or {}
    serve.delete("llm_engine_mixed")

    sp99 = static_row.get("short", {}).get("p99_ms") or 0
    ep99 = engine_row.get("short", {}).get("p99_ms") or 0
    return {
        "model": MIXED_MODEL,
        "arrival_rate_rps": MIXED_RPS,
        "requests": MIXED_N,
        "new_tokens": dict(MIXED_NEW),
        "prompt_lens": {"short": MIXED_SHORT, "long": MIXED_LONG},
        "long_fraction": MIXED_LONG_FRAC,
        "static": static_row,
        "engine": engine_row,
        "engine_ttft_ms_p50": round(ttft["p50"] * 1e3, 1) if ttft else None,
        "engine_ttft_ms_p99": round(ttft["p99"] * 1e3, 1) if ttft else None,
        "engine_tpot_ms_p50": round(tpot["p50"] * 1e3, 2) if tpot else None,
        "engine_tpot_ms_p99": round(tpot["p99"] * 1e3, 2) if tpot else None,
        # the headline: short-request tail latency under long-prompt
        # interference, engine vs static (ROADMAP item 1's p99 cliff)
        "short_p99_ratio_engine_vs_static": round(ep99 / sp99, 3) if sp99 else None,
    }


def _fleet_busy_replica(ray_tpu, name):
    """Index of the replica actively decoding (slots_active > 0) — the
    load snapshots lag, so ask the engines directly."""
    from ray_tpu.serve.api import CONTROLLER_NAME

    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    info = ray_tpu.get(controller.get_handles.remote(name), timeout=30)
    for i, r in enumerate(info["replicas"]):
        try:
            st = ray_tpu.get(
                r.handle_request.remote("engine_stats", (), {}), timeout=30
            )
        except Exception:  # noqa: BLE001 — a booting/dead replica just isn't busy
            continue
        if st.get("slots_active", 0.0) > 0:
            return i
    return -1


def _fleet_stream_trace(ray_tpu, handle, sched, name, kill_at=None):
    """Replay a seeded Poisson arrival schedule as token STREAMS (one
    thread per request, arrivals open-loop), recording client-side TTFT
    per stream.  ``kill_at``: after that many launches, SIGKILL the busy
    replica — every stream must still complete its full budget through
    mid-stream failover."""
    import threading

    from ray_tpu.util import chaos_api

    results: list = []
    errors: list = []
    lock = threading.Lock()

    def _one(prompt):
        t0 = time.time()
        ttft, n = None, 0
        try:
            for fr in handle.stream_tokens(
                {"prompt": prompt, "max_new_tokens": FLEET_NEW}
            ):
                if ttft is None:
                    ttft = time.time() - t0
                n += len(fr)
            with lock:
                results.append((ttft, n))
        except Exception as e:  # noqa: BLE001 — a dropped stream IS the result
            with lock:
                errors.append(f"{type(e).__name__}: {e}")

    threads = []
    t0 = time.time()
    for i, (t_off, _cls, prompt) in enumerate(sched):
        while time.time() - t0 < t_off:
            time.sleep(min(0.002, max(0.0, t_off - (time.time() - t0))))
        th = threading.Thread(target=_one, args=(prompt,), daemon=True)
        th.start()
        threads.append(th)
        if kill_at is not None and i == kill_at:
            idx = _fleet_busy_replica(ray_tpu, name)
            if idx >= 0:
                chaos_api.kill_replica(name, idx)
    for th in threads:
        th.join(600)
    ttfts = np.asarray([t for t, _ in results if t is not None]) * 1000
    return {
        "completed": len(results),
        "full_budget": sum(1 for _, n in results if n == FLEET_NEW),
        "errors": errors,
        "ttft_ms_p99": round(float(np.percentile(ttfts, 99)), 1)
        if len(ttfts)
        else None,
    }


def fleet_survival_bench(ray_tpu, serve):
    """Fleet survival headline numbers (serve/FLEET.md): one seeded
    Poisson stream spike drives an SLO-autoscaled 2-replica engine
    fleet.  Phase 1 (spike, no kill): the spike breaches an aggressive
    latency SLO and the watchdog scales 1→2 — reaction time is spike
    start to the controller's target moving.  Phase 2 (kill): the same
    trace replays against the 2-replica fleet with the busy replica
    SIGKILLed mid-stream — failovers resume every stream from its
    delivered frontier, and the TTFT p99 delta vs phase 1 prices the
    survival machinery."""
    import threading

    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.api import CONTROLLER_NAME
    from ray_tpu.serve.llm import engine_llm_deployment
    from ray_tpu.util import slo_api

    cfg = LlamaConfig(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        vocab_size=256, compute_dtype=jnp.float32, max_seq_len=128,
    )
    dep = engine_llm_deployment(
        cfg, new_tokens=FLEET_NEW, num_slots=4, page_size=16,
        prefill_chunk=16, num_tpus=0, tp=1, name="llm_fleet",
    )
    handle = serve.run(dep.bind())  # 1 replica; the SLO scales it out
    # warm the compile before the clock starts
    _ = [t for fr in handle.stream_tokens(
        {"prompt": [1, 2, 3], "max_new_tokens": 4}) for t in fr]
    rng = np.random.default_rng(7)
    sched = [
        (t, c, [int(x) for x in rng.integers(1, 255, 8)])
        for (t, c, _p) in _poisson_schedule(rng, FLEET_N, FLEET_RPS)
    ]
    controller = ray_tpu.get_actor(CONTROLLER_NAME)

    def _target():
        deps = ray_tpu.get(controller.list_deployments.remote(), timeout=30)
        return deps.get("llm_fleet", {}).get("target", 0)

    # any completed request breaches a 1µs p50 bound → sustained burn →
    # the watchdog publishes ONE scale_out directive per cooldown window
    slo_api.set_slos([{
        "name": "fleet_bench_latency",
        "metric": "ray_tpu_serve_request_seconds",
        "tags": {"deployment": "llm_fleet"},
        "quantile": 0.5,
        "threshold_ms": 0.001,
        "window_s": 60,
        "scale_on_slo": {"deployment": "llm_fleet",
                         "min_replicas": 1, "max_replicas": 2},
    }])
    reaction = [None]
    spike_t0 = time.time()

    def _watch_scale():
        deadline = time.time() + 120
        while time.time() < deadline:
            if _target() >= 2:
                reaction[0] = round(time.time() - spike_t0, 2)
                return
            time.sleep(0.25)

    watcher = threading.Thread(target=_watch_scale, daemon=True)
    watcher.start()
    no_kill = _fleet_stream_trace(ray_tpu, handle, sched, "llm_fleet")
    # the watchdog evaluates windowed DELTAS per observer tick: a spike
    # that completes inside one tick leaves later deltas empty, so keep
    # a trickle flowing until the sustained burn publishes the directive
    trickle_deadline = time.time() + 90
    while reaction[0] is None and time.time() < trickle_deadline:
        try:
            ray_tpu.get(
                handle.remote({"prompt": [5, 6, 7], "max_new_tokens": 2}),
                timeout=60,
            )
        except Exception:  # noqa: BLE001 — trickle is best-effort load
            pass
        time.sleep(0.4)
    watcher.join(10)
    slo_api.clear_slos()
    # wait for the scaled-out fleet to be live before the kill phase
    deadline = time.time() + 60
    while time.time() < deadline and _target() < 2:
        time.sleep(0.5)
    with_kill = _fleet_stream_trace(
        ray_tpu, handle, sched, "llm_fleet", kill_at=FLEET_N // 3
    )
    failovers = 0
    from ray_tpu.experimental.state import summarize_workloads

    deadline = time.time() + 30
    while time.time() < deadline:
        fleet = (summarize_workloads("serve") or {}).get("fleet") or {}
        failovers = int(fleet.get("llm_fleet", {}).get("failovers_total", 0))
        if failovers:
            break
        time.sleep(0.5)
    serve.delete("llm_fleet")
    return {
        "requests_per_phase": FLEET_N,
        "arrival_rate_rps": FLEET_RPS,
        "new_tokens": FLEET_NEW,
        "scale_out_reaction_s": reaction[0],
        "failovers": failovers,
        "ttft_ms_p99_no_kill": no_kill["ttft_ms_p99"],
        "ttft_ms_p99_with_kill": with_kill["ttft_ms_p99"],
        "no_kill": no_kill,
        "with_kill": with_kill,
    }


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")  # the chip is the replica's
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import tpu
    from ray_tpu.serve.llm import llm_deployment

    if not tpu.detect_chips():
        raise SystemExit("bench_serve.py: this host exposes no TPU chip")
    ray_tpu.init(num_cpus=6)

    dep = llm_deployment(
        MODEL,
        max_seq_len=MAX_SEQ,
        new_tokens=NEW_TOKENS,
        max_batch_size=MAX_BATCH,
        batch_wait_timeout_s=0.02,
        num_tpus=1,
        autoscaling_config={
            # engaged: scales on in-flight load, pinned to the one chip
            "min_replicas": 1,
            "max_replicas": 1,
            "target_num_ongoing_requests_per_replica": 32,
        },
    )
    handle = serve.run(dep.bind())
    # warmup: compile the generation program
    t0 = time.time()
    ray_tpu.get(handle.remote(1), timeout=1800)
    compile_s = time.time() - t0
    info = ray_tpu.get(
        serve.get_deployment_handle("llm").method("info").remote(), timeout=60
    )

    loads = [4, 16, 32]
    rows = []
    for concurrency in loads:
        lat: list = []
        t0 = time.time()
        total_requests = concurrency * 4
        done = 0
        inflight = {}
        i = 0
        while done < total_requests:
            while len(inflight) < concurrency and i < total_requests:
                inflight[handle.remote(i)] = time.time()
                i += 1
            ready, _ = ray_tpu.wait(list(inflight), num_returns=1, timeout=600)
            for r in ready:
                start = inflight.pop(r)
                toks = ray_tpu.get(r, timeout=60)
                assert len(toks) == NEW_TOKENS
                lat.append(time.time() - start)
                done += 1
        dt = time.time() - t0
        lat_ms = np.asarray(lat) * 1000
        rows.append(
            {
                "offered_concurrency": concurrency,
                "tokens_per_sec": round(total_requests * NEW_TOKENS / dt, 1),
                "requests_per_sec": round(total_requests / dt, 2),
                "p50_ms": round(float(np.percentile(lat_ms, 50)), 1),
                "p99_ms": round(float(np.percentile(lat_ms, 99)), 1),
            }
        )

    # TTFT/TPOT from the serve request-trace plane: the replica stamps
    # prefill/first-token/decode boundaries per request (serve/tracing.py),
    # the head joins them next to the task flight records, and the summary
    # reports the percentiles — the baseline the continuous-batching
    # engine (ROADMAP item 1) has to beat.
    from ray_tpu.experimental.state import summarize_workloads

    serve_summary = summarize_workloads("serve")
    ttft = serve_summary.get("ttft", {}).get("llm") or {}
    tpot = serve_summary.get("tpot", {}).get("llm") or {}

    result = {
        "metric": "serve_llama_decode_tokens_per_sec_per_chip",
        "value": max(r["tokens_per_sec"] for r in rows),
        "unit": "tokens/s/chip",
        "vs_baseline": 1.0,
        "vs_baseline_basis": "existence (reference publishes no absolute number)",
        "model": MODEL,
        "params_b": info["params_b"],
        "platform": info["platform"],
        "engine": "ShardedLLM tp=%d (donated-cache prefill+decode)" % info["tp"],
        "new_tokens_per_request": NEW_TOKENS,
        "batching": {"max_batch_size": MAX_BATCH, "batch_wait_timeout_s": 0.02},
        "autoscaling_engaged": True,
        "compile_s": round(compile_s, 1),
        "ttft_ms_p50": round(ttft["p50"] * 1e3, 1) if ttft else None,
        "ttft_ms_p99": round(ttft["p99"] * 1e3, 1) if ttft else None,
        "tpot_ms_p50": round(tpot["p50"] * 1e3, 2) if tpot else None,
        "tpot_ms_p99": round(tpot["p99"] * 1e3, 2) if tpot else None,
        "loads": rows,
    }
    if MIXED:
        # side-by-side static vs continuous-batching engine on one seeded
        # mixed-length Poisson trace
        result["mixed_workload"] = mixed_workload_bench(ray_tpu, serve)
    if FLEET:
        # fleet survival: SLO-driven scale-out reaction, failover count
        # and TTFT p99 under a mid-stream replica kill (serve/FLEET.md)
        result["fleet"] = fleet_survival_bench(ray_tpu, serve)
    with open("SERVE_BENCH_r05.json", "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    serve.shutdown()
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
