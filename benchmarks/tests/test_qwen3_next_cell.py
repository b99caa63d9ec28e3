"""What PR 33 added to the benchmark: the long-document saturated mix, the
hybrid model's costs and readers, the ``serve_qwen3_next`` driver's reference
check at the configuration's tiny size, and the tiny rehearsals of the cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs_hybrid
from benchmarks import run as bench_run
from benchmarks.layer_metrics import hybrid_decode_hbm_roofline, hybrid_prefill_roofline, moe_held_assignment_share
from benchmarks.loadgen import closed_loop

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG, CELL = "qwen3-next-80b-a3b-l8-ep4", "qwen3-next-80b-a3b-l8-ep4.longdoc-saturated"


def _load(kind, name):
    return bench_run.load_json(os.path.join(HERE, kind, f"{name}.json"))


def test_the_mix_is_long_documents_in_closed_loop_over_more_callers_than_slots():
    t, cfg = _load("traffic", "longdoc-saturated"), _load("configs", CONFIG)
    assert t["kind"] == "closed_loop" and t["stream"] is False and t["shared_prefix"] == 0
    # the issue's medians; sigma and range narrowed inside what it names, as it provides for (the file's ``lengths_why``)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 4096, "sigma": 0.3, "min": 2048, "max": 8192}
    assert t["output_len"] == {"dist": "lognormal", "median": 128, "sigma": 0.3, "min": 64, "max": 256}
    assert t["clients"] == 24 and t["clients"] * 2 == cfg["engine"]["num_slots"] * 3 and t["clients"] <= cfg["engine"]["max_queue"]
    assert (t["length_block"], t["preroll_s"], t["drain_s"], t["trace_seconds"]) == (8, 8.0, 40.0, 3.0)
    vocab = cfg["vocab_size"]
    a = [closed_loop.request(t, 3_300_000_001, i, vocab) for i in range(32)]
    b = [closed_loop.request(t, 17, i, vocab) for i in range(32)]
    for k in range(4):  # every seed sends each block's lengths, in another order
        blk = slice(8 * k, 8 * k + 8)
        assert sorted(len(r["prompt"]) for r in a[blk]) == sorted(len(r["prompt"]) for r in b[blk])
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    plens, budgets = [len(r["prompt"]) for r in a], [r["budget"] for r in a]
    assert 2048 <= min(plens) and max(plens) <= 8192 and 64 <= min(budgets) and max(budgets) <= 256
    assert max(p + o for p, o in zip(plens, budgets)) <= cfg["engine"]["max_seq_len"]  # every request fits a slot
    assert all(0 <= tok < 37984 for r in a[:4] for tok in r["prompt"])  # ids from the rows held
    assert cfg["engine"]["prefill_chunk"] % 64 == 0


def test_the_configuration_keeps_every_published_width():
    cfg = _load("configs", CONFIG)
    published = dict(hidden_size=2048, head_dim=256, num_attention_heads=16, num_key_value_heads=2, linear_num_key_heads=16,
                     linear_num_value_heads=32, linear_key_head_dim=128, linear_value_head_dim=128, linear_conv_kernel_dim=4,
                     moe_intermediate_size=512, num_experts_published=512, num_experts_per_tok=10, norm_topk_prob=True,
                     shared_expert_intermediate_size=512, partial_rotary_factor=0.25, rope_theta=10000000, full_attention_interval=4)
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (8, 128, 37984) and cfg["reference_layers"] == 4


def test_hybrid_costs_known_answers():
    cfg = _load("configs", CONFIG)
    assert costs_hybrid.num_params(cfg) == 3_667_251_328
    assert costs_hybrid.layer_kinds(cfg) == ["linear", "linear", "linear", "full"] * 2
    assert costs_hybrid.linear_mixer_params(cfg) == 33_718_464 and costs_hybrid.full_mixer_params(cfg) == 27_263_488
    assert costs_hybrid.outside_experts_params(cfg) == 4_200_448 and costs_hybrid.expert_params(cfg) == 3_145_728
    # held experts some row touches, of 128: the issue's three known answers
    for rows, want in ((10, 22.9), (32, 59.9), (256, 127.2)):
        assert costs_hybrid.experts_touched(cfg, rows) == pytest.approx(want, abs=0.05)
    assert costs_hybrid.experts_touched(cfg, 0) == 0.0 and costs_hybrid.experts_touched(cfg, 1) == pytest.approx(2.5)
    assert costs_hybrid.kv_bytes_per_token(cfg) == 4096  # two full layers x K and V x 2 heads x 256 x 2 B
    assert costs_hybrid.state_bytes_per_slot(cfg) == 6 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)  # 12 MiB + 288 KiB
    # a 256-row chunk: 6.40 GB of touched experts + 0.58 of mixers + 0.16 of head; the bytes bind (8.8 ms), not the routed FLOPs (0.9 ms)
    assert costs_hybrid.weight_bytes(cfg, 256) == pytest.approx(7.14e9, rel=5e-3)
    least = costs_hybrid.prefill_chunk_min_seconds(cfg, 256, 4096, PEAKS)
    assert least == pytest.approx((costs_hybrid.weight_bytes(cfg, 256) + 4096 * 4096 + 2 * costs_hybrid.state_bytes_per_slot(cfg)) / 819e9) and 0.0085 < least < 0.0092
    flops = 256 * costs_hybrid.routed_flops_per_token(cfg, 4096 - 128)
    assert 0.0005 < flops / 197e12 < 0.0015
    # a decode step over 10 rows holding 50 000 tokens: 23 of 128 experts a layer, K/V 0.2 GB, state 0.25 GB in and out
    step = costs_hybrid.decode_step_min_bytes(cfg, 10, 50_000)
    assert step == pytest.approx(costs_hybrid.weight_bytes(cfg, 10) + 50_000 * 4096 + 20 * costs_hybrid.state_bytes_per_slot(cfg))
    assert 2.2e9 < step < 2.6e9


def _view(records, ms_decode=None, ms_prefill=None, config=CONFIG, **counters):
    return {"config": _load("configs", config), "records": records, "peaks": PEAKS, "counters": {"window_s": 10.0, **counters},
            "_engine_programs": {"decode": [ms_decode * 1e-3] if ms_decode else [], "prefill": [ms_prefill * 1e-3] if ms_prefill else []}}


def test_new_readers_known_answers_and_nothing_to_read():
    recs = [{"prompt_len": 4000, "tokens": 100, "sent": -1.0, "done": 12.0, "frames": [(12.0, 100)]} for _ in range(8)]
    view = _view(recs, ms_decode=16.0, ms_prefill=16.0, slots_decode_samples=[7.0, 9.0, 8.0], moe_assignments_seen=4000.0, moe_assignments_held=1010.0)
    least = costs_hybrid.decode_step_min_bytes(view["config"], 8.0, 8 * 4050.0) / 819e9
    assert hybrid_decode_hbm_roofline.read(view) == pytest.approx(100 * least / 0.016) and 10 < hybrid_decode_hbm_roofline.read(view) < 25
    # chunks of 4000-token prompts: fifteen of 256 rows and one of 160; ends 256 .. 3840, 4000
    rows = (15 * 256 + 160) / 16
    ends = (sum(256 * (k + 1) for k in range(15)) + 4000) / 16
    want = costs_hybrid.prefill_chunk_min_seconds(view["config"], rows, ends, PEAKS)
    assert hybrid_prefill_roofline.read(view) == pytest.approx(100 * want / 0.016) and 45 < hybrid_prefill_roofline.read(view) < 65
    assert moe_held_assignment_share.read(view) == pytest.approx(0.2525)
    # a parent without the programs or the counters, another configuration, no decode samples, an idle window: nothing, never an error
    bare = _view(recs)
    assert hybrid_decode_hbm_roofline.read(bare) is None and hybrid_prefill_roofline.read(bare) is None and moe_held_assignment_share.read(bare) is None
    other = _view(recs, ms_decode=16.0, ms_prefill=16.0, config="olmoe-1b-7b-l8", slots_decode_samples=[8.0])
    assert hybrid_decode_hbm_roofline.read(other) is None and hybrid_prefill_roofline.read(other) is None
    assert hybrid_decode_hbm_roofline.read(_view(recs, ms_decode=16.0)) is None
    assert hybrid_prefill_roofline.read(_view([], ms_prefill=16.0)) is None
    assert moe_held_assignment_share.read(_view(recs, moe_assignments_seen=0.0, moe_assignments_held=0.0)) is None


def test_reference_check_at_the_configuration_tiny_size():
    from benchmarks.drivers import serve_qwen3_next as driver

    cfg = bench_run.merge_tiny(_load("configs", CONFIG))
    lcfg = driver.hybrid_config(cfg)
    assert (lcfg.n_experts, lcfg.n_routed_experts, lcfg.n_experts_per_tok, lcfg.head_dim, lcfg.layer_kinds) == (4, 16, 3, 32, ("linear", "linear", "linear", "full"))
    for key, bad in (("use_sliding_window", True), ("tie_word_embeddings", True), ("decoder_sparse_step", 2), ("mlp_only_layers", [1])):
        with pytest.raises(ValueError):  # what the program's block cannot compute is refused, not ignored
            driver.hybrid_config({**cfg, key: bad})
    out = driver._reference_check_in_worker(cfg, 3)  # bf16, as the chip runs it; both departures tried inside
    assert out["ok"] and out["as_published_ok"] and out["chunks"] == 3 and out["prompt_len"] > 2 * cfg["engine"]["prefill_chunk"], out
    assert not out["bf16_state"]["ok"] and out["bf16_state"]["rule_alone_err"] > 5 * driver.RULE_TOL
    assert not out["bf16_router"]["ok"] and out["bf16_router"]["router_weight_err"] > 10 * driver.ROUTER_TOL
    # the whole comparison with a bf16 state: not ok
    import dataclasses

    import numpy as np

    from ray_tpu.serve.llm import ShardedLLM

    llm = ShardedLLM(dataclasses.replace(lcfg, n_layers=4), tp=1, seed=3)
    prompt = np.random.default_rng(3).integers(1, lcfg.vocab_size, 150).astype(np.int32)
    with driver.departure("bf16_state"):
        narrowed = driver.compare(llm, prompt, page=8, chunk=64)
    assert not narrowed["ok"] and narrowed["rule_alone_err"] > driver.RULE_TOL, narrowed


def test_a_program_without_the_model_is_refused_before_anything_starts(monkeypatch):
    """The parent commit under this PR's benchmark files: it has no
    ``ray_tpu/models/qwen3_next.py``, and the driver must fail at once."""
    from benchmarks.drivers import serve_moe, serve_qwen3_next

    monkeypatch.setitem(sys.modules, "ray_tpu.models.qwen3_next", None)  # what importing a missing module does
    started = []
    monkeypatch.setattr(serve_moe, "run", lambda ctx: started.append(ctx))
    ctx = bench_run.Context({}, _load("configs", CONFIG), {}, 1, 45.0, False, False, "", "")
    with pytest.raises(ImportError):
        serve_qwen3_next.run(ctx)
    assert not started


def _rehearse(trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL, "--seed", "3300000001", "--seconds", "5", "--trace", str(trace), "--tiny"],
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
    assert len(listed) >= 11 and all(n.endswith(".qwen3next-sat") for n in listed)
    # the device-program readers find no XLA Modules line on the CPU and are left out there, as in the older cells
    on_cpu = {n for n in listed if not n.startswith(("decode_program_ms", "prefill_program_ms", "hybrid_decode", "hybrid_prefill"))}
    out, detail = _rehearse(1)
    assert on_cpu <= set(out["metrics"]), on_cpu - set(out["metrics"])
    ref = detail["notes"]["reference_check"]  # this kind's check ran, with both departures
    assert ref["ok"] and ref["layer_kinds"] == "lllf" and not ref["bf16_state"]["ok"] and not ref["bf16_router"]["ok"]
    c = detail["counters"]  # this kind's replica answered, and the expert kind's client kept its replies
    assert c["state_bytes"] > 0 and c["state_resets"] > 0 and 0 < c["moe_assignments_held"] < c["moe_assignments_seen"]
    assert 0.15 < out["metrics"]["moe_held_assignment_share.qwen3next-sat"]["value"] < 0.4  # 4 of 16 experts held
    assert out["metrics"]["engine_slots_active_unstalled.qwen3next-sat"]["value"] > 3.0
    st = detail["notes"]["stats_end"]
    assert (st["compile_prefill"], st["compile_decode"]) == (1, 1) and st["state_resets"] == st["requests_done"]


def test_the_tiny_untraced_rehearsal_reports_a_throughput_and_set_up():
    out, detail = _rehearse(0)
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0 and detail["counters"]["requests_completed_in_window"] > 0
