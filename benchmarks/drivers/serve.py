"""A serving cell: ``serve.run(engine_llm_deployment(...))`` -> one replica
that holds the chip, driven from this process by the traffic mix's generator.

Everything here runs in the driver process and touches no JAX backend.  What
must be read inside the replica (the device as JAX reports it, the peak
memory, the profiler) is read through three methods that a subclass of the
product's own deployment class adds; the class body, the engine and the model
are the product's, unedited."""

from __future__ import annotations

import importlib
import random
import time
from typing import List, Mapping, Optional

from benchmarks import stats


def llama_config(cfg: Mapping):
    """The program's ``LlamaConfig`` for a configuration file with the
    published (Hugging Face) keys."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise ValueError("LlamaConfig derives head_dim as hidden_size / heads; the file disagrees")
    if cfg.get("sliding_window") or cfg.get("tie_word_embeddings"):
        raise ValueError("the program's llama block has no sliding window and no tied head")
    dtype = jnp.dtype(cfg["torch_dtype"])
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], hidden_dim=cfg["intermediate_size"],
        max_seq_len=cfg["engine"]["max_seq_len"], rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        compute_dtype=dtype, param_dtype=dtype,
    )


def _with_probe(base):
    """``base`` (the class ``engine_llm_deployment`` built) plus read-only
    probes.  Defined in a function so that it is pickled by value."""

    class Probed(base):
        def bench_device(self):
            import jax

            local = jax.local_devices()
            peak = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in local]
            return {"platform": local[0].platform, "kind": local[0].device_kind, "count": len(jax.devices()), "peak_bytes_in_use": max(peak)}

        def bench_trace_start(self, logdir, python_tracer_level):
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = int(python_tracer_level)
            jax.profiler.start_trace(logdir, profiler_options=opts)
            return True

        def bench_trace_stop(self):
            import jax

            jax.profiler.stop_trace()
            return True

    Probed.__name__ = base.__name__
    Probed.__qualname__ = base.__qualname__
    return Probed


PROBE_TIMEOUT_S = 60.0


class Client:
    """What a load generator needs of the system: stream or call.  A probe
    (``method``) that names no timeout waits ``probe_timeout`` seconds."""

    def __init__(self, handle):
        self.handle = handle
        self.probe_timeout = PROBE_TIMEOUT_S

    def stream(self, prompt: List[int], budget: int):
        return self.handle.stream_tokens(prompt, max_new_tokens=int(budget), timeout=120.0)

    def call(self, prompt: List[int], budget: int) -> List[int]:
        import ray_tpu

        return ray_tpu.get(self.handle.remote({"prompt": prompt, "max_new_tokens": int(budget)}), timeout=300)

    def method(self, name: str, *args, timeout: Optional[float] = None):
        import ray_tpu

        return ray_tpu.get(self.handle.method(name).remote(*args), timeout=self.probe_timeout if timeout is None else timeout)


def reference_check(cfg: Mapping, seed: int, chips: int) -> dict:
    """Traced runs only, before ``serve.run``: a TPU actor builds the
    program's ``ShardedLLM`` at the configuration's widths and
    ``reference_layers`` layers, runs a prompt through ``prefill_chunk_paged``
    and one ``decode_step_paged`` over the paged cache, and compares with the
    plain float32 forward pass.  The actor is then killed, which frees the chip."""
    import ray_tpu

    @ray_tpu.remote(num_tpus=chips)
    class RefCheck:
        def run(self, cfg, seed):
            return _reference_check_in_worker(cfg, seed)

    actor = RefCheck.remote()
    try:
        return ray_tpu.get(actor.run.remote(dict(cfg), seed), timeout=900)
    finally:
        ray_tpu.kill(actor)


# Tolerances of the serving comparison, with their reasons.  The program
# computes in bf16 (8 bits of mantissa, relative rounding 2^-9 = 0.002 per
# operation); after two layers the rotated keys and the values agree with the
# float32 reference within a few units of that in RMS, relative to their RMS,
# and the worst of ~700 k elements within KV_MAX_TOL (0.067 measured).  A
# greedy token is right when the reference scores it within LOGIT_TOL of its
# own best token: logits have a standard deviation of about 1.3 here
# (0.02 * sqrt(4096)), so 0.08 is 6% of one deviation; measured errors are in
# PERF.md section 6.  Computing in a narrower type (fp8, int8 weights) would
# miss both by an order of magnitude.
KV_REL_TOL = 0.02
KV_MAX_TOL = 0.15
LOGIT_TOL = 0.08


def _reference_check_in_worker(cfg: Mapping, seed: int) -> dict:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import llama_ref
    from ray_tpu.serve.llm import ShardedLLM

    eng = cfg["engine"]
    lcfg = dataclasses.replace(llama_config(cfg), n_layers=int(cfg["reference_layers"]))
    llm = ShardedLLM(lcfg, tp=int(cfg["layout"]["tp"]), seed=seed % (2**31))
    page, chunk = int(eng["page_size"]), int(eng["prefill_chunk"])
    slots = 2
    plen = chunk + chunk // 3  # two chunks, the second partly padded
    max_len = (plen + page) // page * page + page
    pages_per_slot = max_len // page
    programs = llm.engine_programs(num_pages=slots * pages_per_slot, page_size=page)
    pages = programs["init"]()
    tables = np.full((slots, pages_per_slot), -1, np.int32)
    tables[1] = np.arange(pages_per_slot, dtype=np.int32)[::-1]  # slot 1 owns the first pages, in reverse order
    rng = np.random.default_rng(seed)
    prompt = rng.integers(1, lcfg.vocab_size, plen).astype(np.int32)
    first = None
    for start in range(0, plen, chunk):
        toks = np.zeros(chunk, np.int32)
        n_valid = min(chunk, plen - start)
        toks[:n_valid] = prompt[start : start + n_valid]
        first, pages = programs["prefill"](llm.params, pages, np.ascontiguousarray(tables[1]), toks, np.int32(start), np.int32(n_valid))
    first = int(first)
    tokens = np.zeros(slots, np.int32)
    positions = np.zeros(slots, np.int32)
    active = np.zeros(slots, bool)
    tokens[1], positions[1], active[1] = first, plen, True
    nxt, pages = programs["decode"](llm.params, pages, tables, tokens, positions, active)
    second = int(np.asarray(nxt)[1])

    full = jnp.asarray(np.concatenate([prompt, [first]]))
    logits, keys, values = jax.jit(
        lambda p, t: llama_ref.forward(p, t, n_heads=lcfg.n_heads, n_kv_heads=lcfg.n_kv_heads, rope_theta=lcfg.rope_theta, eps=lcfg.norm_eps)
    )(llm.params, full)
    logits = np.asarray(logits, np.float32)[:, : lcfg.vocab_size]
    pos = np.arange(plen + 1)
    got_k = np.asarray(pages[0].astype(jnp.float32))[:, tables[1][pos // page], pos % page]
    got_v = np.asarray(pages[1].astype(jnp.float32))[:, tables[1][pos // page], pos % page]

    def rel(got, ref):
        """(RMS, largest) error over the RMS of the reference."""
        ref = np.asarray(ref, np.float32)
        scale = np.sqrt((ref**2).mean())
        return float(np.sqrt(((got - ref) ** 2).mean()) / scale), float(np.abs(got - ref).max() / scale)

    (k_rms, k_max), (v_rms, v_max) = rel(got_k, keys), rel(got_v, values)
    out = {
        "layers": lcfg.n_layers,
        "prompt_len": int(plen),
        "k_rel_err": k_rms,
        "v_rel_err": v_rms,
        "k_max_err": k_max,
        "v_max_err": v_max,
        "first_logit_gap": float(logits[plen - 1].max() - logits[plen - 1, first]),
        "second_logit_gap": float(logits[plen].max() - logits[plen, second]),
        "logit_std": float(logits[plen].std()),
        "kv_tol": KV_REL_TOL,
        "kv_max_tol": KV_MAX_TOL,
        "logit_tol": LOGIT_TOL,
        "platform": jax.devices()[0].platform,
    }
    out["ok"] = bool(
        out["k_rel_err"] <= KV_REL_TOL and out["v_rel_err"] <= KV_REL_TOL
        and k_max <= KV_MAX_TOL and v_max <= KV_MAX_TOL
        and out["first_logit_gap"] <= LOGIT_TOL and out["second_logit_gap"] <= LOGIT_TOL
    )
    return out


def deploy_and_warm(cfg: Mapping, seed: int, notes: dict):
    """``serve.run`` the configuration's engine deployment and warm its two
    programs.  Returns the deployment, a client, the replica's ``info``, the
    canary prompt, its answer when sent alone, and the engine's stats."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import engine_llm_deployment

    eng = cfg["engine"]
    dep = engine_llm_deployment(
        llama_config(cfg), new_tokens=eng["default_new_tokens"], num_slots=eng["num_slots"], page_size=eng["page_size"],
        num_pages=eng["num_pages"], prefill_chunk=eng["prefill_chunk"], max_queue=eng["max_queue"],
        num_tpus=int(cfg["chips"]), tp=int(cfg["layout"]["tp"]),
    )
    dep = dep.options(func_or_class=_with_probe(dep.func_or_class))
    t_dep = time.perf_counter()
    handle = serve.run(dep.bind())
    client = Client(handle)
    info = client.method("info", timeout=900)
    notes["replica_ready_s"] = time.perf_counter() - t_dep

    # warm-up: the canary alone compiles the prefill and the decode program;
    # one buffered request warms that route
    rng = random.Random(seed ^ 0x5EED)
    canary_len = min(int(eng["prefill_chunk"]) + int(eng["prefill_chunk"]) // 2, int(eng["max_seq_len"]) // 2)
    canary = [rng.randrange(1, cfg["vocab_size"]) for _ in range(canary_len)]
    t_w = time.perf_counter()
    alone = [t for frame in client.stream(canary, CANARY_BUDGET) for t in frame]
    notes["first_request_s"] = time.perf_counter() - t_w
    client.call(canary[: max(4, canary_len // 8)], 4)
    return dep, client, info, canary, alone, client.method("engine_stats")


CANARY_BUDGET = 16
# ``bench_trace_stop`` writes the profile inside the replica and holds its
# intake meanwhile: the profiler's cost, not the program's.  EVERY deadline of
# a traced run that can meet that stall is its untraced value plus this one
# allowance: a request's drain and, through it, the generator's join of the
# timeline, and a probe's answer (``Client.probe_timeout``).  The stall itself
# is ``notes["trace_stop_s"]``: 27-56 s a cell on the v5e, with the Python
# tracer or without it (PERF.md section 6, PR 43) -- the profiler's own
# collection of what a 3 s capture holds, so a faster engine lengthens it.
TRACE_STALL_ALLOWANCE_S = 30.0
TRACE_STOP_TIMEOUT_S = 300.0  # the stop's own call: one that takes longer than the drain allows shows in the requests


class Probes:
    """The calls a run makes at fixed offsets of its window (``events``), what
    they brought back, and what that says of the run (``judge``).

    The two ``engine_stats`` snapshots at the window's ends, the canary's two
    answers under load and (traced) the profiler's start and stop judge the
    run: one that errs or stays silent is a problem.  ``sample_slots`` (traced,
    twice a second) feeds a per-layer reader and nothing else: a sample that
    gets no answer is a sample missing, counted in the notes."""

    def __init__(self, client, canary: List[int], seconds: float, trace_dir: Optional[str] = None, trace_seconds: float = 3.0):
        self.client, self.canary, self.seconds, self.trace_dir, self.trace_seconds = client, canary, float(seconds), trace_dir, float(trace_seconds)
        self.capture_start_s = self.seconds / 3.0  # a traced run's capture: from here for ``trace_seconds``, then the stop's stall
        self.snaps: dict = {}
        self.answers: dict = {}
        self.slot_samples: List[float] = []
        self.samples_missed: List[str] = []
        self.trace_stop_s: Optional[float] = None

    def snap_start(self):
        self.snaps["start"] = (time.perf_counter(), self.client.method("engine_stats"))

    def snap_end(self):
        self.snaps["end"] = (time.perf_counter(), self.client.method("engine_stats"))

    def canary_stream(self):
        self.answers["loaded_stream"] = [t for frame in self.client.stream(self.canary, CANARY_BUDGET) for t in frame]

    def canary_buffered(self):
        self.answers["loaded_buffered"] = self.client.call(self.canary, CANARY_BUDGET)

    def sample_slots(self):
        try:
            self.slot_samples.append(float(self.client.method("engine_stats")["slots_active"]))
        except Exception as e:  # noqa: BLE001 -- a sample, not a verdict: whatever kept it away, it is one sample fewer
            self.samples_missed.append(f"{type(e).__name__}: {e}"[:200])

    def trace_start(self):
        # no Python tracer: the engine's spans are TraceAnnotations and the dispatch events the runtime's own; it costs the host 0.2-0.6 ms a turn and names gaps by line numbers
        self.client.method("bench_trace_start", self.trace_dir, 0)

    def trace_stop(self):
        t = time.perf_counter()
        self.client.method("bench_trace_stop", timeout=TRACE_STOP_TIMEOUT_S)
        self.trace_stop_s = time.perf_counter() - t

    def events(self) -> list:
        s = self.seconds
        events = [(0.0, self.snap_start), (s, self.snap_end), (s * 0.45, self.canary_stream), (s * 0.55, self.canary_buffered)]
        if self.trace_dir:
            events += [(self.capture_start_s, self.trace_start), (self.capture_start_s + self.trace_seconds, self.trace_stop)]
            events += [(0.25 + 0.5 * k, self.sample_slots) for k in range(int(s * 2))]
        return events

    def judge(self, timeline_errors: List[str], alone: List[int]):
        """(problems, notes) of what the probes brought back."""
        problems, notes = [], {}
        if timeline_errors:
            problems.append(f"timeline: {timeline_errors}")
        for key in ("loaded_stream", "loaded_buffered"):
            if self.answers.get(key) != alone:
                problems.append(f"the canary's answer {key} differs from its answer alone: {self.answers.get(key)} vs {alone}")
        if "start" not in self.snaps or "end" not in self.snaps:
            problems.append("engine_stats snapshots at the window's ends are missing")
        if self.trace_dir:
            notes["trace_stop_s"] = self.trace_stop_s
            notes["slot_samples_taken"], notes["slot_samples_missed"] = len(self.slot_samples), len(self.samples_missed)
            if self.samples_missed:
                notes["slot_samples_missed_first"] = self.samples_missed[0]
            if self.trace_stop_s is None and not timeline_errors:
                problems.append("the profiler's stop had not returned when the run ended")
        return problems, notes


def run(ctx) -> dict:
    from ray_tpu import serve

    cfg, traffic, seconds = ctx.config, ctx.traffic, float(ctx.seconds)
    eng = cfg["engine"]
    chips = int(cfg["chips"])
    notes: dict = {}
    problems: List[str] = []

    if ctx.trace:
        notes["reference_check"] = reference_check(cfg, ctx.seed, chips)
        if not notes["reference_check"]["ok"]:
            problems.append(f"reference check failed: {notes['reference_check']}")

    dep, client, info, canary, alone, stats_warm = deploy_and_warm(cfg, ctx.seed, notes)

    probes = Probes(client, canary, seconds, ctx.trace_dir if ctx.trace else None, float(traffic.get("trace_seconds", 3.0)))
    if ctx.trace:
        client.probe_timeout = PROBE_TIMEOUT_S + TRACE_STALL_ALLOWANCE_S
        traffic = {**traffic, "drain_s": float(traffic.get("drain_s", 10.0)) + TRACE_STALL_ALLOWANCE_S}

    loadgen = importlib.import_module(f"benchmarks.loadgen.{traffic['kind']}")
    ctx.window_opens()
    res = loadgen.run(client, traffic, ctx.seed, seconds, cfg["vocab_size"], probes.events())
    window_epoch = time.time() - (time.perf_counter() - res["t0"])

    stats_end = client.method("engine_stats")
    device = client.method("bench_device")
    t_del = time.perf_counter()
    serve.delete(dep.name)
    serve.shutdown()
    notes["delete_s"] = time.perf_counter() - t_del

    # ---- judge
    recs = res["records"]
    if traffic["kind"] == "open_loop":
        measured = [r for r in recs if 0.0 <= r["due"] < seconds]
    else:
        measured = [r for r in recs if r["sent"] is not None and r["sent"] >= 0.0]
    bad = [r for r in measured if r["error"] or r["done"] is None or r["tokens"] != r["budget"]]
    bad_ids = {id(r) for r in bad}
    measured_ids = {id(r) for r in measured}
    pre_bad = [r for r in recs if id(r) not in measured_ids and (r["error"] or (r["done"] is not None and r["tokens"] != r["budget"]))]
    if bad:
        problems.append(f"{len(bad)} of {len(measured)} requests failed, e.g. {[(r['i'], r['error'], r['tokens'], r['budget']) for r in bad[:3]]}")
    if pre_bad:
        problems.append(f"{len(pre_bad)} pre-roll requests failed")
    probe_problems, probe_notes = probes.judge(res["timeline_errors"], alone)
    problems += probe_problems
    notes.update(probe_notes)
    # when the last request came back, from the window's start: what a traced run's drain (drain_s + the allowance) has to hold
    notes["last_request_done_s"] = max((r["done"] for r in recs if r["done"] is not None), default=None)
    notes["drain_limit_s"] = seconds + float(traffic["drain_s"]) if "drain_s" in traffic else None
    if len(alone) != CANARY_BUDGET:
        problems.append(f"the canary returned {len(alone)} tokens, not {CANARY_BUDGET}")
    for name, st in (("warm-up", stats_warm), ("end", stats_end)):
        if (st["compile_prefill"], st["compile_decode"]) != (1, 1):
            problems.append(f"compile counts after {name}: prefill {st['compile_prefill']}, decode {st['compile_decode']}")
    if stats_end["requests_failed"]:
        problems.append(f"engine requests_failed = {stats_end['requests_failed']}")
    if device["count"] != chips or info["platform"] != device["platform"]:
        problems.append(f"replica device {device}, cell asks for {chips} chip(s)")

    e2e = {}
    ttft = [(r["frames"][0][0] - r["due"]) * 1e3 for r in measured if r["due"] is not None and r["frames"] and id(r) not in bad_ids]
    gaps = [g for r in measured if id(r) not in bad_ids for g in stats.token_gaps_ms(r["frames"])]
    if ttft and traffic.get("stream"):
        e2e["ttft_p90_ms"] = (stats.percentile(ttft, 90), "ms")
        e2e["ttft_p50_ms"] = (stats.percentile(ttft, 50), "ms")
        e2e["ttft_p75_ms"] = (stats.percentile(ttft, 75), "ms")
        e2e["ttft_mean_ms"] = (sum(ttft) / len(ttft), "ms")
    if gaps:
        e2e["token_gap_p95_ms"] = (stats.percentile(gaps, 95), "ms")
        e2e["token_gap_p50_ms"] = (stats.percentile(gaps, 50), "ms")
    done_in = [r for r in recs if r["done"] is not None and 0.0 <= r["done"] <= seconds and not r["error"]]
    e2e["serve_tokens_per_s"] = (sum(r["prompt_len"] + r["tokens"] for r in done_in) / seconds, "tokens/s")

    lag = [(r["sent"] - r["due"]) * 1e3 for r in measured if r["due"] is not None and r["sent"] is not None]
    s0, s1 = probes.snaps.get("start"), probes.snaps.get("end")
    counters = {
        "window_s": seconds,
        "loadgen_lag_ms": lag,
        "slot_samples": probes.slot_samples,
        "requests_measured": len(measured),
        "requests_completed_in_window": len(done_in),
        "ttft_samples": len(ttft),
        "gap_samples": len(gaps),
        "num_slots": int(eng["num_slots"]),
        "max_seq_len": int(eng["max_seq_len"]),
    }
    if ctx.trace:
        counters["capture_start_s"] = probes.capture_start_s  # what was due well before it met neither the profiler nor its stall (``layer_metrics/_ttft.py``)
    if s0 and s1:
        counters["stats_interval_s"] = s1[0] - s0[0]
        counters["iterations"] = s1[1]["iterations"] - s0[1]["iterations"]
        counters["tokens_generated"] = s1[1]["tokens_generated"] - s0[1]["tokens_generated"]
    # live context of the decode fleet, for the bytes a decode step must read:
    # mean tokens held by the requests in flight, from what the client saw
    notes.update(info={k: info[k] for k in ("platform", "params_b", "tp")}, stats_end=stats_end, peak_bytes_in_use=device["peak_bytes_in_use"])
    return {
        "correct": not problems,
        "problems": problems,
        "attempted": len(measured),
        "failed": len(bad),
        "window_epoch": window_epoch,
        "e2e": e2e,
        "device": {"platform": device["platform"], "kind": device["kind"], "count": device["count"], "memory_peak_bytes": device["peak_bytes_in_use"]},
        "counters": counters,
        "records": recs,
        "notes": notes,
        # idle gaps are named on the thread that holds the engine's turns, by the innermost ``engine/*`` span there (the program's own vocabulary: no line numbers)
        "host_thread": r"^engine/iteration$",
        "gap_names": r"^engine/",
    }
