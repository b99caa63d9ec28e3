"""What PR 35 added to the benchmark: the reasoning-saturated mix, the
state-space hybrid's costs and readers, the ``serve_jamba`` driver's reference
check at the configuration's tiny size, and the tiny rehearsals of the cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs_ssm
from benchmarks import run as bench_run
from benchmarks.layer_metrics import _ssm_kernel, ssm_decode_hbm_roofline, ssm_prefill_roofline, ssm_scan_kernel_share, ssm_scan_roofline
from benchmarks.loadgen import closed_loop

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG, CELL = "jamba2-3b", "jamba2-3b.reasoning-saturated"


def _load(kind, name):
    return bench_run.load_json(os.path.join(HERE, kind, f"{name}.json"))


def test_the_mix_is_short_questions_and_long_answers_in_closed_loop_over_more_callers_than_slots():
    t, cfg = _load("traffic", "reasoning-saturated"), _load("configs", CONFIG)
    assert t["kind"] == "closed_loop" and t["stream"] is False and t["shared_prefix"] == 0
    assert t["prompt_len"] == {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 64, "max": 1024}
    assert t["output_len"] == {"dist": "lognormal", "median": 512, "sigma": 0.5, "min": 128, "max": 1024}
    assert t["clients"] == 192 and t["clients"] * 2 == cfg["engine"]["num_slots"] * 3 and t["clients"] <= cfg["engine"]["max_queue"]
    assert (t["length_block"], t["preroll_s"], t["drain_s"], t["trace_seconds"]) == (16, 16.0, 40.0, 3.0)
    vocab = cfg["vocab_size"]
    a = [closed_loop.request(t, 3_500_000_001, i, vocab) for i in range(64)]
    b = [closed_loop.request(t, 17, i, vocab) for i in range(64)]
    for k in range(4):  # every seed sends each block's lengths, in another order
        blk = slice(16 * k, 16 * k + 16)
        assert sorted(len(r["prompt"]) for r in a[blk]) == sorted(len(r["prompt"]) for r in b[blk])
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    plens, budgets = [len(r["prompt"]) for r in a], [r["budget"] for r in a]
    assert 64 <= min(plens) and max(plens) <= 1024 and 128 <= min(budgets) and max(budgets) <= 1024
    assert max(plens) + max(budgets) <= cfg["engine"]["max_seq_len"]  # every request fits a slot
    assert 0.6 < sum(budgets) / (sum(plens) + sum(budgets)) < 0.7  # about two thirds of the tokens are generated
    assert max(tok for r in a[:8] for tok in r["prompt"]) > 60_000  # ids from the whole vocabulary


def test_the_configuration_is_the_published_one_with_nothing_reduced():
    cfg = _load("configs", CONFIG)
    published = {"attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1, "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
                 "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
                 "mamba_proj_bias": False, "max_position_embeddings": 262144, "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
                 "num_experts_per_tok": 1, "num_hidden_layers": 28, "num_key_value_heads": 1, "num_logits_to_keep": 1, "rms_norm_eps": 1e-06,
                 "sliding_window": None, "tie_word_embeddings": True, "use_mamba_kernels": True, "vocab_size": 65536}
    assert {k: cfg[k] for k in published} == published  # every key of the catalog row's config, at the top level
    assert cfg["reduced"] == [] and cfg["chips"] == 1 and cfg["kind"] == "serve_jamba"
    assert {"layer_kinds", "state", "fused_projections"} <= set(cfg["assumed"])
    eng = cfg["engine"]
    assert (eng["num_slots"], eng["max_seq_len"], eng["page_size"], eng["num_pages"], eng["prefill_chunk"], eng["max_queue"]) == (128, 2048, 16, 0, 256, 256)
    assert cfg["reference_layers"] == {"num_hidden_layers": 4, "attn_layer_period": 4, "attn_layer_offset": 2}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert entry["reduced"] == [] and entry["source"] == cfg["source"] and len(bench["workloads"]) == 7 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_ssm_costs_known_answers():
    cfg = _load("configs", CONFIG)
    assert costs_ssm.num_params(cfg) == 3_029_337_472 and costs_ssm.layer_kinds(cfg).count("mamba") == 26
    assert [i for i, k in enumerate(costs_ssm.layer_kinds(cfg)) if k == "attn"] == [7, 21]
    assert (costs_ssm.mamba_mixer_params(cfg), costs_ssm.attn_mixer_params(cfg), costs_ssm.ffn_params(cfg)) == (41_241_792, 13_762_560, 62_919_680)
    assert costs_ssm.weight_bytes(cfg) == 2 * 3_029_337_472  # every weight once; the tied matrix once
    assert costs_ssm.kv_bytes_per_token(cfg) == 1024  # two attending layers x K and V x one head of 128 x 2 B
    assert costs_ssm.state_bytes_per_slot(cfg) == 26 * (5120 * 16 * 4 + 3 * 5120 * 2)  # 8.52 MB + 0.80 MB
    # the issue's decode step: 128 rows holding ~100 k tokens: 8.5 GB, 10.4 ms; 0.78 TFLOP, 3.9 ms at peak
    step = costs_ssm.decode_step_min_bytes(cfg, 128, 100_000)
    assert step == pytest.approx(costs_ssm.weight_bytes(cfg) + 100_000 * 1024 + 256 * costs_ssm.state_bytes_per_slot(cfg)) and 8.4e9 < step < 8.7e9
    assert 0.70e12 < 128 * costs_ssm.flops_per_token(cfg, 800) < 0.80e12
    # a 256-row chunk: FLOPs (7.5 ms at peak) just over the weights' bytes (7.4 ms)
    least = costs_ssm.prefill_chunk_min_seconds(cfg, 256, 256, PEAKS)
    assert 0.0073 < least < 0.0077 and least == pytest.approx((256 * costs_ssm.flops_per_token(cfg, 128) + 2 * 2560 * 65536) / 197e12)
    assert costs_ssm.prefill_chunk_min_seconds(cfg, 100, 100, PEAKS) == pytest.approx((costs_ssm.weight_bytes(cfg) + 100 * 1024 + 2 * costs_ssm.state_bytes_per_slot(cfg)) / 819e9)
    # the kernel alone: a chunk's bytes are its u, dt and y (15.7 MB: 20 us), a decode step's the states in and out (84 MB: 112 us)
    assert costs_ssm.scan_min_seconds(cfg, 256, 1, PEAKS) == pytest.approx(4 * (256 * (3 * 5120 + 32) + 2 * 5120 * 16 + 5120 * 17) / 819e9)
    assert 110e-6 < costs_ssm.scan_min_seconds(cfg, 128, 128, PEAKS) < 115e-6
    assert costs_ssm.scan_flops_per_row(cfg) == 5120 * (7 * 16 + 3)


def _view(records, ms_decode=None, ms_prefill=None, config=CONFIG, op_s=None, op_count=None, busy_s=1.0, **counters):
    return {"config": _load("configs", config), "records": records, "peaks": PEAKS, "counters": {"window_s": 10.0, **counters},
            "trace": {"op_s": op_s or {}, "op_count": op_count or {}, "busy_s": busy_s},
            "_engine_programs": {"decode": [ms_decode * 1e-3] if ms_decode else [], "prefill": [ms_prefill * 1e-3] if ms_prefill else []}}


def test_new_readers_known_answers_and_nothing_to_read():
    recs = [{"prompt_len": 300, "tokens": 500, "sent": -1.0, "done": 12.0, "frames": [(12.0, 500)]} for _ in range(8)]
    ops = {"jit_prefill_chunk_paged/ssm_scan.3": 0.0026, "jit_prefill_chunk_paged/ssm_scan.7": 0.0026, "jit_decode_step_paged/ssm_scan.3": 0.0150, "jit_decode_step_paged/fusion.9": 0.5}
    counts = {"jit_prefill_chunk_paged/ssm_scan.3": 26, "jit_prefill_chunk_paged/ssm_scan.7": 26, "jit_decode_step_paged/ssm_scan.3": 100, "jit_decode_step_paged/fusion.9": 100}
    view = _view(recs, ms_decode=16.0, ms_prefill=15.0, op_s=ops, op_count=counts, busy_s=0.9, slots_decode_samples=[120.0, 124.0, 122.0])
    least = costs_ssm.decode_step_min_bytes(view["config"], 122.0, 122 * 550.0) / 819e9
    assert ssm_decode_hbm_roofline.read(view) == pytest.approx(100 * least / 0.016) and 55 < ssm_decode_hbm_roofline.read(view) < 70
    # chunks of 300-token prompts: one of 256 rows and one of 44; ends 256, 300
    want = costs_ssm.prefill_chunk_min_seconds(view["config"], 150.0, 278.0, PEAKS)
    assert ssm_prefill_roofline.read(view) == pytest.approx(100 * want / 0.015) and 45 < ssm_prefill_roofline.read(view) < 55
    assert _ssm_kernel.calls(view["trace"]) == {"prefill": (pytest.approx(0.0052), 52), "decode": (0.0150, 100)}
    least = 52 * costs_ssm.scan_min_seconds(view["config"], 150.0, 1.0, PEAKS) + 100 * costs_ssm.scan_min_seconds(view["config"], 122.0, 122.0, PEAKS)
    assert ssm_scan_roofline.read(view) == pytest.approx(100 * least / 0.0202) and 40 < ssm_scan_roofline.read(view) < 70
    assert ssm_scan_kernel_share.read(view) == pytest.approx(100 * 0.0202 / 0.9)
    # a parent without the programs, the kernel or the counters; another configuration; no decode samples: nothing, never an error
    bare = _view(recs)
    assert all(r.read(bare) is None for r in (ssm_decode_hbm_roofline, ssm_prefill_roofline, ssm_scan_roofline, ssm_scan_kernel_share))
    other = _view(recs, ms_decode=16.0, ms_prefill=15.0, config="mistral-7b-l16", op_s=ops, op_count=counts, slots_decode_samples=[8.0])
    assert ssm_decode_hbm_roofline.read(other) is None and ssm_prefill_roofline.read(other) is None and ssm_scan_roofline.read(other) is None
    assert ssm_decode_hbm_roofline.read(_view(recs, ms_decode=16.0)) is None and ssm_scan_roofline.read(_view(recs, op_s=ops, op_count=counts)) is None
    assert ssm_prefill_roofline.read(_view([], ms_prefill=15.0)) is None


def test_reference_check_at_the_configuration_tiny_size():
    from benchmarks.drivers import serve_jamba as driver

    cfg = bench_run.merge_tiny(_load("configs", CONFIG))
    lcfg = driver.jamba_config(cfg)
    assert (lcfg.dim, lcfg.d_inner, lcfg.d_state, lcfg.dt_rank, lcfg.head_dim, lcfg.layer_kinds) == (64, 128, 8, 8, 16, ("mamba", "mamba", "attn", "mamba"))
    for key, bad in (("sliding_window", 4096), ("tie_word_embeddings", False), ("num_experts", 16), ("mamba_proj_bias", True), ("mamba_conv_bias", False), ("hidden_act", "gelu")):
        with pytest.raises(ValueError):  # what the program's block cannot compute is refused, not ignored
            driver.jamba_config({**cfg, key: bad})
    whole = driver.jamba_config(_load("configs", CONFIG))
    assert whole.num_params() == 3_029_337_472 and driver.reference_config(_load("configs", CONFIG)).layer_kinds == ("mamba", "mamba", "attn", "mamba")
    out = driver._reference_check_in_worker(cfg, 3)  # bf16, as the chip runs it; the departure tried inside
    assert out["ok"] and out["as_published_ok"] and out["chunks"] == 3 and out["prompt_len"] > 2 * cfg["engine"]["prefill_chunk"], out
    assert not out["bf16_state"]["ok"] and out["bf16_state"]["rule_alone_err"] > 5 * driver.RULE_TOL


def test_a_program_without_the_model_is_refused_before_anything_starts(monkeypatch):
    """The parent commit under this PR's benchmark files: it has no
    ``ray_tpu/models/jamba.py``, and the driver must fail at once."""
    from benchmarks.drivers import serve, serve_jamba

    monkeypatch.setitem(sys.modules, "ray_tpu.models.jamba", None)  # what importing a missing module does
    started = []
    monkeypatch.setattr(serve, "run", lambda ctx: started.append(ctx))
    ctx = bench_run.Context({}, _load("configs", CONFIG), {}, 1, 45.0, False, False, "", "")
    with pytest.raises(ImportError):
        serve_jamba.run(ctx)
    assert not started


def _rehearse(trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL, "--seed", "3500000001", "--seconds", "5", "--trace", str(trace), "--tiny"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    return out, json.loads(lines[-2].split("detail:", 1)[1])


def test_the_tiny_traced_rehearsal_of_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert len(listed) >= 12 and all(n.endswith(".jamba2-sat") for n in listed)
    assert all(m["moves"] == "serve_tokens_per_s" and m["workloads"] == [CELL] for m in bench["per_layer"] if m["name"] in listed)
    # the device-program and kernel readers find no XLA Modules line and no Pallas call on the CPU and are left out there, as in the older cells
    on_cpu = {n for n in listed if not n.startswith(("decode_program_ms", "prefill_program_ms", "ssm_"))}
    out, detail = _rehearse(1)
    assert on_cpu <= set(out["metrics"]), on_cpu - set(out["metrics"])
    ref = detail["notes"]["reference_check"]  # this kind's check ran, with its departure
    assert ref["ok"] and ref["layer_kinds"] == "mmam" and not ref["bf16_state"]["ok"]
    c = detail["counters"]  # this kind's replica answered, and the stats-keeping client kept its replies
    assert c["state_bytes"] == 4 * 3 * (8 * 128 * 4 + 3 * 128 * 2) and c["state_resets"] > 0 and c["slots_active_unstalled_n"] > 0
    assert out["metrics"]["engine_slots_active_unstalled.jamba2-sat"]["value"] > 3.0
    st = detail["notes"]["stats_end"]
    assert (st["compile_prefill"], st["compile_decode"]) == (1, 1) and st["state_resets"] == st["requests_done"] and "moe_expert_load" not in st


def test_the_tiny_untraced_rehearsal_reports_a_throughput_and_set_up():
    out, detail = _rehearse(0)
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0 and detail["counters"]["requests_completed_in_window"] > 0
