"""What PR 45 added to the benchmark: the document-reasoning saturated mix, the
latent-attention model's costs and readers, the ``serve_mla_moe`` driver's
reference check at the configuration's tiny size, and the tiny rehearsals of
the cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs_mla
from benchmarks import run as bench_run
from benchmarks.layer_metrics import mla_decode_hbm_roofline, mla_latent_read_share, mla_prefill_roofline
from benchmarks.loadgen import closed_loop

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG, CELL = "moonlight-16b-a3b-l8", "moonlight-16b-a3b-l8.docreason-saturated"


def _load(kind, name):
    return bench_run.load_json(os.path.join(HERE, kind, f"{name}.json"))


def test_the_mix_is_one_document_a_request_in_closed_loop_over_more_callers_than_slots():
    t, cfg = _load("traffic", "docreason-saturated"), _load("configs", CONFIG)
    assert t["kind"] == "closed_loop" and t["stream"] is False and t["shared_prefix"] == 0
    # the issue's medians, sigmas and ranges; the drain is longer than it names (the file's ``drain_why``: at 40 s requests fail)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 3072, "sigma": 0.3, "min": 1536, "max": 6144}
    assert t["output_len"] == {"dist": "lognormal", "median": 512, "sigma": 0.3, "min": 256, "max": 1024}
    assert t["clients"] == 96 and t["clients"] * 2 == cfg["engine"]["num_slots"] * 3 and t["clients"] <= cfg["engine"]["max_queue"]
    assert (t["length_block"], t["preroll_s"], t["drain_s"], t["trace_seconds"]) == (16, 20.0, 75.0, 3.0)
    vocab = cfg["vocab_size"]
    a = [closed_loop.request(t, 3_300_000_001, i, vocab) for i in range(32)]
    b = [closed_loop.request(t, 17, i, vocab) for i in range(32)]
    for k in range(2):  # every seed sends each block's lengths, in another order
        blk = slice(16 * k, 16 * k + 16)
        assert sorted(len(r["prompt"]) for r in a[blk]) == sorted(len(r["prompt"]) for r in b[blk])
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    plens, budgets = [len(r["prompt"]) for r in a], [r["budget"] for r in a]
    assert 1536 <= min(plens) and max(plens) <= 6144 and 256 <= min(budgets) and max(budgets) <= 1024
    assert max(p + o for p, o in zip(plens, budgets)) <= 7168 < cfg["engine"]["max_seq_len"]  # every request fits a slot
    assert max(tok for r in a[:4] for tok in r["prompt"]) > 100_000  # ids from the whole vocabulary


def test_the_configuration_keeps_every_published_key_but_the_depth():
    cfg = _load("configs", CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(d for d in map(json.loads, f) if d["name"] == "Moonlight-16B-A3B")
    assert cfg["source"] == published["source_url"]
    assert {k: cfg[k] for k in published["config"] if k != "num_hidden_layers"} == {k: v for k, v in published["config"].items() if k != "num_hidden_layers"}
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"], cfg["reduced"]) == (8, 27, ["num_hidden_layers"])
    assert cfg["reference_layers"] >= 3 and cfg["first_k_dense_replace"] == 1  # the dense layer and two of experts are compared
    eng = cfg["engine"]
    assert (eng["num_slots"], eng["max_seq_len"], eng["page_size"], eng["prefill_chunk"]) == (64, 8192, 16, 256)
    assert eng["num_pages"] * eng["page_size"] >= eng["num_slots"] * 3750  # 64 requests of the mix's mean reservation


def test_mla_costs_against_a_count_by_hand():
    cfg = _load("configs", CONFIG)
    # W_q 2048x3072 + W_dkv 2048x576 + latent norm 512 + W_ukv 512x4096 + W_o 2048x2048, and the block's two norms
    assert costs_mla.attention_params(cfg) == 6_291_456 + 1_179_648 + 512 + 2_097_152 + 4_194_304 + 4096 == 13_763_072 + 4096
    assert costs_mla.expert_params(cfg) == 8_650_752 and costs_mla.dense_ffn_params(cfg) == 3 * 2048 * 11264
    assert costs_mla.outside_experts_params(cfg) == 2048 * 64 + 64 + 3 * 2048 * 2816
    assert costs_mla.num_params(cfg) == 4_847_999_424 and costs_mla.num_params({**cfg, "num_hidden_layers": 27}) == 15_960_110_208
    assert costs_mla.cache_bytes_per_position(cfg) == 8 * 576 * 2 == 9216  # as K and V heads: 8 x 16 x (192 + 128) x 2 = 81 920
    for rows, want in ((44, 63.16), (256, 64.0), (1, 6.0), (0, 0.0)):
        assert costs_mla.experts_touched(cfg, rows) == pytest.approx(want, abs=0.01)
    # a decode step of 44 rows over 145 000 positions: 9.0 GB of weights (every expert touched) + 1.34 GB of latent rows
    assert costs_mla.weight_bytes(cfg, 44) == pytest.approx(8.92e9, rel=5e-3)
    step = costs_mla.decode_step_min_bytes(cfg, 44, 145_000)
    assert step == pytest.approx(costs_mla.weight_bytes(cfg, 44) + 145_000 * 9216) and 12.0e-3 < step / 819e9 < 13.0e-3
    # a token's FLOPs at context 1600: 2 x (8 x 13.76 M of attention + 69.2 M dense + 7 x (0.13 M router + 17.3 M shared + 6 x 8.65 M)) + 8 x 10 240 x 1600
    matmul = 8 * 13_763_072 - 8 * 512 + 3 * 2048 * 11264 + 7 * (2048 * 64 + 3 * 2048 * 2816 + 6 * 8_650_752)
    assert costs_mla.routed_flops_per_token(cfg, 1600) == pytest.approx(2 * matmul + 8 * 10_240 * 1600)
    # a 256-row chunk at context 1700: bytes bind (10.9 ms of weights + 16 MB of rows), not the routed FLOPs (1.9 ms)
    least = costs_mla.prefill_chunk_min_seconds(cfg, 256, 1700, PEAKS)
    assert least == pytest.approx((costs_mla.weight_bytes(cfg, 256) + 1700 * 9216) / 819e9) and 0.0108 < least < 0.0112


def _view(records, ms_decode=None, ms_prefill=None, config=CONFIG, **counters):
    return {"config": _load("configs", config), "records": records, "peaks": PEAKS, "counters": {"window_s": 10.0, **counters},
            "_engine_programs": {"decode": [ms_decode * 1e-3] if ms_decode else [], "prefill": [ms_prefill * 1e-3] if ms_prefill else []}}


def test_new_readers_known_answers_and_nothing_to_read():
    recs = [{"prompt_len": 3000, "tokens": 500, "sent": -1.0, "done": 12.0, "frames": [(12.0, 500)]} for _ in range(8)]
    live = dict(slots_decode_samples=[43.0, 45.0, 44.0], ctx_positions_live=14_500_000.0, decode_steps=100.0, cache_bytes_per_position=9216.0)
    view = _view(recs, ms_decode=20.0, ms_prefill=16.0, **live)
    least = costs_mla.decode_step_min_bytes(view["config"], 44.0, 145_000.0) / 819e9
    assert mla_decode_hbm_roofline.read(view) == pytest.approx(100 * least / 0.020) and 55 < mla_decode_hbm_roofline.read(view) < 70
    assert mla_latent_read_share.read(view) == pytest.approx(100 * 145_000 * 9216 / (costs_mla.weight_bytes(view["config"], 44.0) + 145_000 * 9216))
    assert 12 < mla_latent_read_share.read(view) < 14
    # chunks of 3000-token prompts: eleven of 256 rows and one of 184; ends 256 .. 2816, 3000
    rows, ends = (11 * 256 + 184) / 12, (sum(256 * (k + 1) for k in range(11)) + 3000) / 12
    want = costs_mla.prefill_chunk_min_seconds(view["config"], rows, ends, PEAKS)
    assert mla_prefill_roofline.read(view) == pytest.approx(100 * want / 0.016) and 60 < mla_prefill_roofline.read(view) < 75
    # a parent without the counters or the programs, another configuration, no samples, no steps: nothing, never an error
    bare = _view(recs)
    assert mla_decode_hbm_roofline.read(bare) is None and mla_prefill_roofline.read(bare) is None and mla_latent_read_share.read(bare) is None
    other = _view(recs, ms_decode=20.0, ms_prefill=16.0, config="olmoe-1b-7b-l8", **live)
    assert mla_decode_hbm_roofline.read(other) is None and mla_prefill_roofline.read(other) is None and mla_latent_read_share.read(other) is None
    assert mla_decode_hbm_roofline.read(_view(recs, ms_decode=20.0, slots_decode_samples=[44.0])) is None
    assert mla_decode_hbm_roofline.read(_view(recs, ms_decode=20.0, **{**live, "decode_steps": 0.0})) is None
    assert mla_prefill_roofline.read(_view([], ms_prefill=16.0)) is None


def test_reference_check_at_the_configuration_tiny_size():
    from benchmarks.drivers import serve_mla_moe as driver

    cfg = bench_run.merge_tiny(_load("configs", CONFIG))
    lcfg = driver.mla_config(cfg)
    assert (lcfg.n_experts, lcfg.n_experts_per_tok, lcfg.latent_dim, lcfg.first_k_dense, lcfg.n_kv_heads, lcfg.routed_scaling_factor) == (8, 3, 40, 1, 1, 2.446)
    for key, bad in (("q_lora_rank", 1536), ("n_group", 8), ("scoring_func", "softmax"), ("tie_word_embeddings", True), ("num_nextn_predict_layers", 1), ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError):  # what the program's block cannot compute is refused, not ignored
            driver.mla_config({**cfg, key: bad})
    out = driver._reference_check_in_worker(cfg, 3)  # bf16, as the chip runs it; every departure tried inside
    assert out["ok"] and out["as_published_ok"] and out["departures_passed"] == [] and out["chunks"] == 2, out
    assert all(not out[which]["ok"] for which in driver.DEPARTURES) and out["bias_decides_share"] > 0.3
    assert (out["latent_dim"], out["row_dim"], out["pool_members"]) == (40, 128, 2) and not out["padding_written"] and out["prompt_len"] > cfg["engine"]["prefill_chunk"]
    control = driver._reference_check_in_worker(cfg, 3, control=True)  # the limits' second reading: fp8 weights against the weights themselves
    assert not control["ok"] and control["row_rel_err"] > 3 * out["row_rel_err"], control


def test_a_program_without_the_model_is_refused_before_anything_starts(monkeypatch):
    """The parent commit under this PR's benchmark files: it has no
    ``ray_tpu/models/deepseek_v3.py``, and the driver must fail at once."""
    from benchmarks.drivers import serve_mla_moe, serve_moe

    monkeypatch.setitem(sys.modules, "ray_tpu.models.deepseek_v3", None)  # what importing a missing module does
    started = []
    monkeypatch.setattr(serve_moe, "run", lambda ctx: started.append(ctx))
    ctx = bench_run.Context({}, _load("configs", CONFIG), {}, 1, 45.0, False, False, "", "")
    with pytest.raises(ImportError):
        serve_mla_moe.run(ctx)
    assert not started


def _rehearse(trace):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL, "--seed", "3300000001", "--seconds", "5", "--trace", str(trace), "--tiny"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0, lines[-2][:3000]
    return out, json.loads(lines[-2].split("detail:", 1)[1])


def test_the_tiny_traced_rehearsal_of_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert len(listed) == 13 and all(n.endswith(".moonlight-sat") for n in listed)
    # the device-program readers find no XLA Modules line on the CPU and are left out there, as in the older cells
    on_cpu = {n for n in listed if not n.startswith(("decode_program_ms", "prefill_program_ms", "mla_decode", "mla_prefill"))}
    out, detail = _rehearse(1)
    assert on_cpu <= set(out["metrics"]), on_cpu - set(out["metrics"])
    ref = detail["notes"]["reference_check"]  # this kind's check ran, with every departure
    assert ref["ok"] and (ref["latent_dim"], ref["row_dim"]) == (40, 128) and ref["departures_passed"] == []
    c = detail["counters"]  # this kind's replica answered, and the expert kind's client kept its replies
    assert c["cache_bytes_per_position"] == 3 * 128 * 2 and c["ctx_positions_live"] > 0 and c["decode_steps"] > 0 and c["moe_assignments"] > 0
    assert 0 < out["metrics"]["mla_latent_read_share.moonlight-sat"]["value"] < 100
    assert out["metrics"]["engine_slots_active_unstalled.moonlight-sat"]["value"] > 3.0
    st = detail["notes"]["stats_end"]
    assert (st["compile_prefill"], st["compile_decode"]) == (1, 1)


def test_the_tiny_untraced_rehearsal_reports_a_throughput_and_set_up():
    out, detail = _rehearse(0)
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0 and detail["counters"]["requests_completed_in_window"] > 0


def test_the_kind_judges_a_run_by_serve_moes_rule_and_only_adds_its_counters(monkeypatch):
    """``run`` hands on what ``serve_moe.run`` found -- a snapshot asked late
    stays that run's problem, as for every expert kind -- and adds the live
    positions of the decode steps between the replies nearest the capture."""
    import types

    from benchmarks.drivers import serve_mla_moe as driver
    from benchmarks.drivers import serve_moe

    late = f"no engine_stats reply with routing counters within {serve_moe.SNAPSHOT_SLACK_S} s of each end of the window"
    reply = lambda steps: {"ctx_positions_live": 3000.0 * steps, "decode_steps": float(steps), "cache_bytes_per_position": 10240.0}  # noqa: E731
    found = {"problems": [late], "correct": False, "window_epoch": 1000.0, "counters": {}, "notes": {}}
    monkeypatch.setattr(serve_moe, "run", lambda ctx: found)
    monkeypatch.setattr(serve_moe._Client, "stats_log", [(1000.1, reply(10)), (1015.2, reply(400)), (1018.1, reply(500)), (1045.64, reply(1200))])
    ctx = types.SimpleNamespace(config=_load("configs", CONFIG), seconds=45.0, traffic={"trace_seconds": 3.0}, trace=1)
    out = driver.run(ctx)
    assert not out["correct"] and out["problems"] == [late]
    assert out["counters"] == {"ctx_positions_live": 300000.0, "decode_steps": 100.0, "cache_bytes_per_position": 10240.0}
    ctx.trace = 0  # untraced: between the window's two ends
    assert driver.run(ctx)["counters"]["decode_steps"] == 1190.0
