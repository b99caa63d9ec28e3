"""Workload-plane observability: serve request traces, train-step probe,
memory accounting, and the SLO watchdog.

Covers the serve request-trace join (stage stamps propagate ingress →
replica → engine and sum to ≈ e2e, TTFT < total), the
StepProbe breakdown + jitter/MFU stats, memory-gauge aggregation
(`ray-tpu summary memory` + /metrics scrape), SLO window math
(pure-function unit tests) and the watchdog end-to-end (a deliberately
breached SLO emits a RECORD_EVENT that lands on the chrome timeline),
plus the RAY_TPU_TASK_EVENTS=0 no-stamp contract extended to the serve
and train sites.
"""

import time
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def ray_cluster():
    info = ray_tpu.init(num_cpus=4)
    yield info
    ray_tpu.shutdown()


def _serve_summary(limit=0):
    from ray_tpu.experimental.state import summarize_workloads

    return summarize_workloads("serve", limit=limit)


def _llm_handle(new_tokens=4):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve import llm as llm_mod

    cfg = LlamaConfig(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        vocab_size=256, compute_dtype=jnp.float32,
    )
    dep = llm_mod.engine_llm_deployment(
        cfg, max_seq_len=32, new_tokens=new_tokens, num_slots=4, page_size=4,
        prefill_chunk=4, num_tpus=0, tp=1,
    )
    return serve.run(dep.bind())


def test_serve_request_trace_join(ray_cluster):
    """End-to-end through the real serve path (handle → replica → the
    engine's admission, prefill and decode): the head joins per-stage
    spans whose sum ≈ e2e, TTFT is populated and strictly under the
    total, and TPOT is per-token."""
    new_tokens = 4
    handle = _llm_handle(new_tokens=new_tokens)
    refs = [handle.remote(i) for i in range(3)]
    results = ray_tpu.get(refs, timeout=300)
    assert all(len(seq) == new_tokens for seq in results)
    from ray_tpu.serve import tracing as serve_tracing

    # records ship batched from the replica; force any tail flush by a
    # follow-up request, then poll the head
    deadline = time.time() + 60
    reply = {}
    while time.time() < deadline:
        reply = _serve_summary(limit=50)
        if reply["total_records"] >= 3:
            break
        ray_tpu.get(handle.remote(1), timeout=60)
        time.sleep(0.3)
    records = [r for r in reply.get("records", []) if r["name"] == "serve:llm"]
    assert len(records) >= 3, f"serve flight records missing: {reply}"
    for rec in records:
        ph = rec["phases"]
        for stamp in (
            "serve_proxy_recv",
            "serve_route",
            "serve_replica_recv",
            "serve_engine_submit",
            "serve_engine_admit",
            "serve_prefill_start",
            "serve_first_token",
            "serve_decode_end",
            "serve_handler_end",
        ):
            assert stamp in ph, f"{stamp} missing from {sorted(ph)}"
        durs = rec["durations"]
        e2e = durs["serve_e2e"]
        # the named stages partition the e2e window (route + deliver +
        # replica-side handler); allow slack for the unstamped slivers
        # (result serialization, scheduling gaps)
        stage_sum = (
            durs["serve_route"]
            + durs["serve_deliver"]
            + durs["serve_handler"]
        )
        assert stage_sum <= e2e + 0.005
        assert stage_sum >= 0.5 * e2e, (stage_sum, e2e, durs)
        inner = (
            durs["serve_engine_queue"]
            + durs["serve_prefill_wait"]
            + durs["serve_prefill"]
            + durs["serve_decode"]
        )
        assert inner <= durs["serve_handler"] + 0.005
        # TTFT: populated, after the start, strictly before the end
        assert rec["ttft_s"] is not None and 0.0 <= rec["ttft_s"] < e2e
        assert rec["tpot_s"] is not None and rec["tpot_s"] >= 0.0
        assert rec["tokens"] == new_tokens
    # aggregated surfaces: per-stage table + TTFT/TPOT percentiles
    stages = {(r["deployment"], r["stage"]) for r in reply["summary"]}
    for stage in ("serve_engine_queue", "serve_prefill", "serve_decode", "serve_e2e"):
        assert ("llm", stage) in stages, stages
    assert reply["ttft"]["llm"]["count"] >= 3
    assert reply["tpot"]["llm"]["count"] >= 3
    # stage histograms land in the shared metrics namespace
    from ray_tpu.util import metrics as metrics_mod

    merged = metrics_mod.read_all()
    fams = {metrics_mod.parse_series_key(k)[0] for k in merged}
    assert "ray_tpu_serve_request_seconds" in fams
    assert "ray_tpu_serve_ttft_seconds" in fams
    assert "ray_tpu_serve_tpot_seconds" in fams
    # timeline: serve sub-spans render like task phases
    events = ray_tpu.timeline()
    sub = {
        e["name"].split(":", 2)[-1]
        for e in events
        if e.get("cat") == "task_phase" and e["name"].startswith("serve:llm:")
    }
    assert {"serve_engine_queue", "serve_prefill", "serve_decode"} <= sub, sub
    serve.shutdown()


def test_train_step_probe(ray_cluster):
    """StepProbe: per-phase breakdown joins at the head, rolling stats
    carry jitter (and MFU when flops are declared), and `summary train`
    reports both."""
    from ray_tpu.experimental.state import summarize_workloads
    from ray_tpu.train.jax import StepProbe

    probe = StepProbe(
        "unit_run", flops_per_step=1e9, peak_flops_per_device=1e12
    )
    for _ in range(6):
        with probe.step():
            with probe.phase("data_wait"):
                time.sleep(0.002)
            with probe.phase("h2d"):
                pass
            with probe.phase("compute"):
                time.sleep(0.004)
                probe.block(np.zeros(4))
            with probe.phase("metrics_fold"):
                pass
    probe.flush()
    st = probe.stats()
    assert st["steps"] == 6
    assert st["p99_s"] >= st["p50_s"] > 0
    assert "jitter_pct" in st and st["jitter_pct"] >= 0
    assert 0 < st["mfu"] < 1  # 1e9 flops / (step_s * 1e12)
    deadline = time.time() + 30
    reply = {}
    while time.time() < deadline:
        reply = summarize_workloads("train", limit=10)
        if reply["total_records"] >= 6 and "unit_run" in reply.get("runs", {}):
            break
        time.sleep(0.2)
    assert reply["total_records"] >= 6, reply
    rows = {(r["run"], r["phase"]) for r in reply["summary"]}
    for phase in ("train_data_wait", "train_compute", "train_step"):
        assert ("unit_run", phase) in rows, rows
    run_stats = reply["runs"]["unit_run"]
    assert run_stats["steps"] >= 6
    assert "jitter_pct" in run_stats and "mfu" in run_stats
    # breakdown invariant: phases nest inside the step
    for rec in reply["records"]:
        durs = rec["durations"]
        inner = sum(
            durs.get(k, 0.0)
            for k in ("train_data_wait", "train_h2d", "train_compute", "train_metrics_fold")
        )
        assert inner <= durs["train_step"] + 0.005
    # rolling gauges reached the metrics namespace
    from ray_tpu.util import metrics as metrics_mod

    merged = metrics_mod.read_all()
    fams = {metrics_mod.parse_series_key(k)[0] for k in merged}
    assert "ray_tpu_train_step_jitter_pct" in fams
    assert "ray_tpu_train_mfu" in fams


def test_memory_summary_and_gauges(ray_cluster):
    """`summary memory`: per-node shm occupancy, object accounting by
    state/owner, spill counters; the same numbers reach /metrics as
    ray_tpu_shm_* / ray_tpu_object_* gauges (scrape smoke)."""
    from ray_tpu.experimental.state import summarize_workloads

    refs = [ray_tpu.put(np.zeros(1024, np.uint8)) for _ in range(4)]
    # driver refcounts reach the head on the batched ADD_REF flush
    # (~0.2s cadence): poll until the pins land
    deadline = time.time() + 15
    reply = {}
    while time.time() < deadline:
        reply = summarize_workloads("memory")
        if reply["objects"]["pinned"] >= 4:
            break
        time.sleep(0.2)
    nodes = reply["nodes"]
    assert nodes, reply
    head = next(iter(nodes.values()))
    assert head["capacity"] > 0 and head["used"] > 0
    obj = reply["objects"]
    assert obj["total"] >= 4
    assert obj["by_state"]["SEALED"] >= 4
    assert obj["pinned"] >= 4  # our refs hold them
    assert obj["by_owner"], "owner accounting empty"
    owner_bytes = sum(o["bytes"] for o in obj["by_owner"].values())
    assert owner_bytes >= 4 * 1024
    del refs
    # gauges: wait for an observer tick, then scrape the head's /metrics
    addr = ray_tpu.nodes()[0]["Labels"].get("metrics_addr")
    assert addr
    deadline = time.time() + 30
    text = ""
    while time.time() < deadline:
        with urllib.request.urlopen(f"http://{addr}/metrics", timeout=30) as r:
            text = r.read().decode()
        if "ray_tpu_shm_used_bytes" in text:
            break
        time.sleep(0.5)
    assert "ray_tpu_shm_used_bytes{" in text
    assert "ray_tpu_shm_capacity_bytes{" in text
    assert 'ray_tpu_object_count{state="SEALED"}' in text
    assert "ray_tpu_object_pinned_count" in text
    # the whole scrape is structurally valid exposition format
    from ray_tpu.tools.prom_validate import validate

    errors = validate(text)
    assert not errors, errors


# ------------------------------------------------------------------ SLOs


def test_slo_window_math_unit():
    """Pure window math: quantile interpolation, violating fraction,
    burn rate, and windowed deltas vs lifetime counts."""
    from ray_tpu._private import slo

    bounds = [0.01, 0.1, 1.0]
    # 90 fast + 10 slow observations
    buckets = [90, 0, 10, 0]
    q = slo.estimate_quantile(bounds, buckets, 0.5)
    assert 0.0 < q <= 0.01
    q99 = slo.estimate_quantile(bounds, buckets, 0.99)
    assert 0.1 < q99 <= 1.0
    assert slo.estimate_quantile(bounds, [0, 0, 0, 0], 0.99) is None
    vf = slo.violating_fraction(bounds, buckets, 0.1)
    assert abs(vf - 0.10) < 1e-9
    assert slo.burn_rate(0.10, 0.99) == pytest.approx(10.0)
    # windowed evaluator: old observations age out of the verdict
    spec = slo.parse_specs(
        [
            {
                "name": "u",
                "metric": "m",
                "tags": {},
                "quantile": 0.9,
                "threshold_ms": 100,
                "window_s": 10,
            }
        ]
    )[0]
    ev = slo.SloEvaluator(spec)

    def rec(buckets):
        return {
            "m:": {
                "name": "m",
                "kind": "histogram",
                "boundaries": bounds,
                "buckets": list(buckets),
                "sum": 0.0,
                "count": sum(buckets),
                "tags": {},
            }
        }

    # t=0: 100 slow observations (lifetime fallback on the first tick)
    v0 = ev.evaluate(rec([0, 0, 100, 0]), now=0.0)
    assert not v0["ok"] and v0["samples"] == 100
    # t=5: 100 fast observations arrive; window delta sees ONLY them
    v1 = ev.evaluate(rec([100, 0, 100, 0]), now=5.0)
    assert v1["ok"] and v1["samples"] == 100
    assert v1["value"] <= 0.1
    # gauge spec
    gspec = slo.parse_specs(
        [{"name": "g", "gauge": "jit", "max": 25.0, "window_s": 5}]
    )[0]
    gev = slo.SloEvaluator(gspec)
    gv = gev.evaluate(
        {"jit:": {"name": "jit", "kind": "gauge", "value": 40.0, "tags": {}, "ts": 1.0}},
        now=1.0,
    )
    assert not gv["ok"] and gv["burn_rate"] == pytest.approx(40.0 / 25.0)
    # spec validation rejects garbage loudly
    with pytest.raises(ValueError):
        slo.parse_specs([{"name": "bad"}])
    with pytest.raises(ValueError):
        slo.parse_specs([{"name": "bad", "metric": "m", "quantile": 2.0, "threshold_ms": 1}])


def test_slo_breach_event_and_timeline_marker(ray_cluster):
    """A deliberately-unmeetable SLO breaches within a watchdog tick:
    `ray-tpu slo` reports it, ray_tpu_slo_* gauges export, and the breach
    lands as an instant marker on the chrome timeline (source=slo) —
    alongside the task spans, like chaos events."""
    from ray_tpu.experimental.state import slo_status
    from ray_tpu.util import slo_api

    slo_api.set_slos(
        [
            {
                # exec p50 must beat 1µs — any real task breaches it
                "name": "task_exec_unmeetable",
                "metric": "ray_tpu_task_phase_seconds",
                "tags": {"phase": "exec"},
                "quantile": 0.5,
                "threshold_ms": 0.001,
                "window_s": 60,
            }
        ]
    )

    @ray_tpu.remote
    def busy():
        time.sleep(0.02)
        return 1

    assert ray_tpu.get([busy.remote() for _ in range(4)], timeout=60) == [1] * 4
    deadline = time.time() + 30
    verdict = None
    while time.time() < deadline:
        reply = slo_status()
        slos = {s["name"]: s for s in reply.get("slos", [])}
        verdict = slos.get("task_exec_unmeetable")
        if verdict is not None and not verdict["ok"]:
            break
        time.sleep(0.5)
    assert verdict is not None and not verdict["ok"], verdict
    assert verdict["burn_rate"] > 1.0
    assert verdict["samples"] >= 4
    # breach marker on the timeline, next to the task spans
    events = ray_tpu.timeline()
    marks = [e for e in events if e.get("cat") == "event:slo"]
    assert marks, "slo breach marker missing from timeline"
    assert any("task_exec_unmeetable" in m["name"] for m in marks)
    assert any(e.get("cat") == "task" for e in events)
    # exported gauges
    from ray_tpu.util import metrics as metrics_mod

    merged = metrics_mod.read_all()
    ok_rec = merged.get("ray_tpu_slo_ok:slo=task_exec_unmeetable")
    burn_rec = merged.get("ray_tpu_slo_burn_rate:slo=task_exec_unmeetable")
    assert ok_rec is not None and ok_rec["value"] == 0.0
    assert burn_rec is not None and burn_rec["value"] > 1.0


def test_workload_recording_disabled_no_stamps(monkeypatch, shutdown_only):
    """RAY_TPU_TASK_EVENTS=0 contract extended to the workload planes:
    no serve trace is minted at the ingress, the replica adds no stamps,
    the StepProbe is a shared no-op context, and the head joins zero
    serve/train records."""
    monkeypatch.setenv("RAY_TPU_TASK_EVENTS", "0")
    from ray_tpu._private import task_events
    from ray_tpu.serve import tracing as serve_tracing

    task_events.set_enabled(False)
    try:
        # ingress: one flag check, no record
        assert serve_tracing.new_request("x") is None
        # probe: shared no-op context objects, no allocation per step
        from ray_tpu.train.jax import StepProbe
        from ray_tpu.train.jax.step_probe import _NULL

        probe = StepProbe("off_run", flops_per_step=1e9)
        assert probe.step() is _NULL
        with probe.step():
            assert probe.phase("compute") is _NULL
        probe.flush()
        assert probe.stats()["steps"] == 0

        ray_tpu.init(num_cpus=4)
        handle = _llm_handle(new_tokens=2)
        out = ray_tpu.get(handle.remote(1), timeout=300)
        assert len(out) == 2
        from ray_tpu.experimental.state import summarize_workloads

        time.sleep(1.0)
        assert summarize_workloads("serve")["total_records"] == 0
        assert summarize_workloads("train")["total_records"] == 0
        serve.shutdown()
    finally:
        task_events.set_enabled(True)


def test_summary_memory_cli_shape(ray_cluster):
    """The memory summary carries everything the CLI renders (guards the
    cmd_summary field contract)."""
    from ray_tpu.experimental.state import summarize_workloads

    reply = summarize_workloads("memory")
    assert set(reply) >= {"nodes", "objects", "dag_channels"}
    assert set(reply["objects"]) >= {
        "by_state", "by_owner", "pinned", "total", "spilled", "lineage",
    }


def test_prom_validator_unit():
    """The exposition validator catches each malformation class and
    passes well-formed text."""
    from ray_tpu.tools.prom_validate import validate

    good = (
        "# HELP m help\n# TYPE m counter\n"
        'm{a="1"} 3\nm{a="2"} 4\n'
        "# TYPE h histogram\n"
        'h_bucket{le="0.1"} 1\nh_bucket{le="+Inf"} 2\n'
        "h_sum 0.5\nh_count 2\n"
    )
    assert validate(good) == []
    assert any("no preceding # TYPE" in e for e in validate("m 1\n"))
    dup = "# TYPE m counter\nm{a=\"1\"} 1\nm{a=\"1\"} 2\n"
    assert any("duplicate series" in e for e in validate(dup))
    bad_label = '# TYPE m counter\nm{a="un\nescaped"} 1\n'
    assert any(
        "unparseable" in e or "no preceding" in e for e in validate(bad_label)
    )
    no_inf = '# TYPE h histogram\nh_bucket{le="0.1"} 1\nh_count 1\n'
    assert any('+Inf' in e for e in validate(no_inf))
    shrinking = (
        "# TYPE h histogram\n"
        'h_bucket{le="0.1"} 5\nh_bucket{le="+Inf"} 3\nh_count 3\n'
    )
    assert any("decreases" in e for e in validate(shrinking))
    dup_type = "# TYPE m counter\n# TYPE m counter\nm 1\n"
    assert any("duplicate # TYPE" in e for e in validate(dup_type))
