"""What PR 28 added to the benchmark: the saturated chat mix, the expert
model's costs and readers, the ``serve_moe`` driver's reference check at the
configuration's tiny size, and the tiny rehearsal of the new cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs, costs_moe
from benchmarks import run as bench_run
from benchmarks.layer_metrics import engine_slots_active_unstalled, moe_decode_hbm_roofline, moe_expert_load_imbalance, moe_prefill_roofline
from benchmarks.loadgen import closed_loop, open_loop

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _load(kind, name):
    return bench_run.load_json(os.path.join(HERE, kind, f"{name}.json"))


def test_the_saturated_mix_is_chat_lengths_in_closed_loop_over_more_callers_than_slots():
    t, chat, cfg = _load("traffic", "chat-saturated"), _load("traffic", "chat"), _load("configs", "olmoe-1b-7b-l8")
    assert t["kind"] == "closed_loop" and t["stream"] is False and t["shared_prefix"] == 0
    assert t["prompt_len"] == chat["prompt_len"] and t["output_len"] == chat["output_len"]  # chat's own distributions
    assert t["clients"] == 48 and t["clients"] * 2 == cfg["engine"]["num_slots"] * 3 and t["clients"] <= cfg["engine"]["max_queue"]
    assert t["length_block"] in (8, 16, 32) and (t["preroll_s"], t["drain_s"], t["trace_seconds"]) == (8.0, 40.0, 3.0)
    # every seed sends each block's lengths in another order, and the same lengths: the tokens offered up
    # to any block boundary are equal across seeds, which is what keeps the completed tokens steady
    size = t["length_block"]
    a = [closed_loop.request(t, 3_000_000_001, i, 50304) for i in range(4 * size)]
    b = [closed_loop.request(t, 17, i, 50304) for i in range(4 * size)]
    assert a == [closed_loop.request(t, 3_000_000_001, i, 50304) for i in range(4 * size)]
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    for k in range(4):
        blk = slice(k * size, (k + 1) * size)
        assert sorted(len(r["prompt"]) for r in a[blk]) == sorted(len(r["prompt"]) for r in b[blk])
        assert sorted(r["budget"] for r in a[blk]) == sorted(r["budget"] for r in b[blk])
    plens, budgets = [len(r["prompt"]) for r in a], [r["budget"] for r in a]
    assert 32 <= min(plens) and max(plens) <= 1024 and 16 <= min(budgets) and max(budgets) <= 256
    # the same lengths as chat's (its median prompt within 40 tokens), and every request fits a slot
    chat_lens = sorted(len(r["prompt"]) for r in open_loop.schedule(chat, 1, 45.0, 32768))
    assert abs(sorted(plens)[len(plens) // 2] - chat_lens[len(chat_lens) // 2]) < 40
    assert max(p + o for p, o in zip(plens, budgets)) <= cfg["engine"]["max_seq_len"]


def test_expert_costs_count_routed_work_and_touched_experts():
    cfg = _load("configs", "olmoe-1b-7b-l8")
    # every expert's weights at 32 rows and more: 8 layers x 64 x 3 x 2048 x 1024 x 2 B = 6.44 GB of 6.91
    assert costs_moe.experts_touched(cfg, 256) == pytest.approx(64.0, rel=1e-9) and 63.0 < costs_moe.experts_touched(cfg, 32) < 64.0
    assert costs_moe.experts_touched(cfg, 1) == pytest.approx(8.0) and costs_moe.experts_touched(cfg, 0) == 0.0
    assert costs_moe.weight_bytes(cfg, 256) == pytest.approx(6.917e9, rel=2e-3)
    assert costs_moe.weight_bytes(cfg, 256) - costs_moe.weight_bytes(cfg, 0) == pytest.approx(6.4425e9, rel=1e-4)
    assert costs_moe.kv_bytes_per_token(cfg) == 64 * 1024
    # a routed token: top-8, not 64 -- 0.538 B matmul parameters in 8 layers, against 3.36 B with every expert
    assert costs_moe.routed_flops_per_token(cfg, 0) == pytest.approx(2 * 8 * (4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024))
    # a chunk of 256 rows: the weight bytes (8.5 ms) bind, not the routed FLOPs (1.5 ms)
    least = costs_moe.prefill_chunk_min_seconds(cfg, 256, 256, PEAKS)
    assert least == pytest.approx((costs_moe.weight_bytes(cfg, 256) + 256 * 65536) / 819e9) and 0.008 < least < 0.009
    # with one expert a token and the dense widths the formulas are the dense ones
    dense = _load("configs", "mistral-7b-l16")
    as_moe = {**dense, "num_experts": 1, "num_experts_per_tok": 1}
    extra = (dense["num_hidden_layers"] * (dense["hidden_size"] + dense["hidden_size"] + 1024)) * 2  # router column and QK-norm scales
    assert costs_moe.weight_bytes(as_moe, 4) == pytest.approx(costs.llama_weight_bytes(dense) + extra)


def _view(records, ms_decode=None, ms_prefill=None, **counters):
    cfg = _load("configs", "olmoe-1b-7b-l8")
    return {"config": cfg, "records": records, "peaks": PEAKS, "counters": {"window_s": 10.0, **counters},
            "_engine_programs": {"decode": [ms_decode * 1e-3] if ms_decode else [], "prefill": [ms_prefill * 1e-3] if ms_prefill else []}}


def test_roofline_readers_known_answers_and_nothing_to_read():
    # buffered requests (one frame, at completion): 20 slots decoding, each holding 300 + 100/2 tokens
    recs = [{"prompt_len": 300, "tokens": 100, "sent": -1.0, "done": 12.0, "frames": [(12.0, 100)]} for _ in range(20)]
    view = _view(recs, ms_decode=20.0, ms_prefill=17.0, slots_decode_samples=[19.0, 21.0, 20.0])
    assert moe_decode_hbm_roofline.occupancy(view) == (20.0, 7000.0)
    least = costs_moe.decode_step_min_bytes(view["config"], 20.0, 7000.0) / 819e9
    assert moe_decode_hbm_roofline.read(view) == pytest.approx(100 * least / 0.020) and 40 < moe_decode_hbm_roofline.read(view) < 50
    # a request weighs by its answer's tokens (one decode turn a token): 100 x 350 and 300 x 250 over 400
    mixed = _view(recs[:1] + [{"prompt_len": 100, "tokens": 300, "done": 3.0, "frames": [(3.0, 300)]}], ms_decode=20.0, slots_decode_samples=[2.0])
    assert moe_decode_hbm_roofline.occupancy(mixed) == (2.0, 2 * (100 * 350.0 + 300 * 250.0) / 400)
    # chunks of 300-token prompts: 256 rows ending at 256, 44 rows ending at 300
    want = costs_moe.prefill_chunk_min_seconds(view["config"], 150.0, 278.0, PEAKS)
    assert moe_prefill_roofline.read(view) == pytest.approx(100 * want / 0.017) and 45 < moe_prefill_roofline.read(view) < 55
    # a parent without the programs, a dense configuration, no decode samples, an idle window: nothing, never an error
    assert moe_decode_hbm_roofline.read(_view(recs)) is None and moe_prefill_roofline.read(_view(recs)) is None
    dense = {**view, "config": _load("configs", "mistral-7b-l16")}
    assert moe_decode_hbm_roofline.read(dense) is None and moe_prefill_roofline.read(dense) is None
    assert moe_decode_hbm_roofline.read(_view(recs, ms_decode=20.0)) is None
    assert moe_decode_hbm_roofline.read(_view([], ms_decode=20.0, slots_decode_samples=[3.0])) is None
    assert moe_expert_load_imbalance.read(_view(recs)) is None
    assert moe_expert_load_imbalance.read(_view(recs, moe_expert_load=[0] * 64)) is None
    assert moe_expert_load_imbalance.read(_view(recs, moe_expert_load=[100] * 63 + [163])) == pytest.approx(163 / (6463 / 64))
    assert engine_slots_active_unstalled.read(_view(recs)) is None
    assert engine_slots_active_unstalled.read(_view(recs, slots_active_unstalled=[32.0, 31.0, 32.0, 31.0])) == 31.5


def test_olmoe_reference_check_at_the_configuration_tiny_size():
    from benchmarks.drivers import serve_moe

    cfg = bench_run.merge_tiny(_load("configs", "olmoe-1b-7b-l8"))
    lcfg = serve_moe.moe_config(cfg)
    assert (lcfg.n_experts, lcfg.n_experts_per_tok, lcfg.qk_norm) == (8, 2, True)
    for key in ("norm_topk_prob", "attention_bias", "clip_qkv"):  # what the program's block cannot compute is refused, not ignored
        with pytest.raises(ValueError):
            serve_moe.moe_config({**cfg, key: True})
    out = serve_moe._reference_check_in_worker(cfg, 3)  # bf16, as the chip runs it
    assert out["prompt_len"] > cfg["engine"]["prefill_chunk"] and out["ok"], out
    cfg["torch_dtype"] = "float32"
    exact = serve_moe._reference_check_in_worker(cfg, 3)
    assert exact["ok"] and exact["k_rel_err"] < 1e-4 and exact["v_rel_err"] < 1e-4 and exact["router_weight_err"] < 1e-5, exact
    assert exact["moe_load_total"] == (exact["prompt_len"] + 1) * 2 * 2 and exact["moe_load_miscount"] == 0


def test_a_program_without_experts_is_refused_before_anything_starts(monkeypatch):
    """The parent commit under this PR's benchmark files: its LlamaConfig has
    no such keys, and the driver must fail at once, not serve a dense model."""
    import dataclasses

    from benchmarks.drivers import serve_moe
    from ray_tpu.models import llama

    @dataclasses.dataclass(frozen=True)
    class ParentConfig:
        vocab_size: int = 32000
        dim: int = 4096
        n_layers: int = 32
        n_heads: int = 32
        n_kv_heads: int = 32
        hidden_dim: int = 11008
        max_seq_len: int = 2048
        rope_theta: float = 10000.0
        norm_eps: float = 1e-5
        compute_dtype: object = None
        param_dtype: object = None

    monkeypatch.setattr(llama, "LlamaConfig", ParentConfig)
    ctx = bench_run.Context({}, _load("configs", "olmoe-1b-7b-l8"), {}, 1, 45.0, False, False, "", "")
    with pytest.raises(TypeError, match="n_experts"):
        serve_moe.run(ctx)


CELL = "olmoe-1b-7b-l8.chat-saturated"


def _rehearse(trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL, "--seed", "3000000001", "--seconds", "5", "--trace", str(trace), "--tiny"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    return out, json.loads(lines[-2].split("detail:", 1)[1])


def test_the_tiny_traced_rehearsal_of_the_new_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert len(listed) >= 10 and all(n.endswith(".olmoe-sat") for n in listed)
    # the device-program readers find no XLA Modules line on the CPU and are left out there, as in the older cells
    on_cpu = {n for n in listed if not n.startswith(("decode_program_ms", "prefill_program_ms", "moe_decode_hbm", "moe_prefill"))}
    out, detail = _rehearse(1)
    assert on_cpu <= set(out["metrics"]), on_cpu - set(out["metrics"])
    ref = detail["notes"]["reference_check"]
    assert ref["ok"] and ref["experts"] == 8 and ref["routing_flips_above_margin"] == 0
    assert detail["counters"]["moe_assignments"] > 0 and out["metrics"]["moe_expert_load_imbalance.olmoe-sat"]["value"] >= 1.0
    # six callers over four slots: the replica is full whenever it is asked, up to the capture's end
    # (what the profiler's stall does afterwards is left out of this reading: layer_metrics/engine_slots_active_unstalled.py)
    assert out["metrics"]["engine_slots_active_unstalled.olmoe-sat"]["value"] > 3.0
    assert 0 < detail["counters"]["slots_active_unstalled_n"] <= 10  # the window's start and the half-second samples up to the capture's end


def test_the_tiny_untraced_rehearsal_reports_a_throughput_and_set_up():
    out, detail = _rehearse(0)
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0 and detail["counters"]["requests_completed_in_window"] > 0
