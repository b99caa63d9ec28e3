"""What PR 47 added to the benchmark: the agent-turn saturated mix, the
short-convolution expert model's costs and readers, the ``serve_conv_moe``
driver's reference check at the configuration's tiny size, and the tiny
rehearsals of the cell."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks import costs_conv
from benchmarks import run as bench_run
from benchmarks.layer_metrics import conv_decode_hbm_roofline, conv_kv_read_share, conv_prefill_roofline
from benchmarks.loadgen import closed_loop

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG, CELL = "lfm2-24b-a2b-l10", "lfm2-24b-a2b-l10.agentturn-saturated"


def _load(kind, name):
    return bench_run.load_json(os.path.join(HERE, kind, f"{name}.json"))


def test_the_mix_is_an_agents_turn_in_closed_loop_over_more_callers_than_slots():
    t, cfg = _load("traffic", "agentturn-saturated"), _load("configs", CONFIG)
    assert t["kind"] == "closed_loop" and t["stream"] is False and t["shared_prefix"] == 0
    # the issue's medians, sigmas and ranges, not narrowed
    assert t["prompt_len"] == {"dist": "lognormal", "median": 1536, "sigma": 0.4, "min": 512, "max": 4096}
    assert t["output_len"] == {"dist": "lognormal", "median": 512, "sigma": 0.4, "min": 192, "max": 1024}
    assert t["clients"] == 144 and t["clients"] * 2 == cfg["engine"]["num_slots"] * 3 and t["clients"] <= cfg["engine"]["max_queue"]
    assert (t["length_block"], t["preroll_s"], t["trace_seconds"]) == (16, 20.0, 3.0) and t["drain_s"] >= 75.0
    vocab = cfg["vocab_size"]
    a = [closed_loop.request(t, 3_300_000_001, i, vocab) for i in range(32)]
    b = [closed_loop.request(t, 17, i, vocab) for i in range(32)]
    for k in range(2):  # every seed sends each block's lengths, in another order
        blk = slice(16 * k, 16 * k + 16)
        assert sorted(len(r["prompt"]) for r in a[blk]) == sorted(len(r["prompt"]) for r in b[blk])
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    plens, budgets = [len(r["prompt"]) for r in a], [r["budget"] for r in a]
    assert 512 <= min(plens) and max(plens) <= 4096 and 192 <= min(budgets) and max(budgets) <= 1024
    assert max(p + o for p, o in zip(plens, budgets)) <= 5120 < cfg["engine"]["max_seq_len"]  # every request fits a slot
    assert max(tok for r in a[:4] for tok in r["prompt"]) > 60_000  # ids from the whole vocabulary


def test_the_configuration_keeps_every_published_key_but_the_depth():
    cfg = _load("configs", CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        published = next(d for d in map(json.loads, f) if d["name"] == "LFM2-24B-A2B")
    assert cfg["source"] == published["source_url"]
    # every key as published, nested groups whole (layer_types all forty; the program runs its first num_hidden_layers)
    assert {k: cfg[k] for k in published["config"] if k != "num_hidden_layers"} == {k: v for k, v in published["config"].items() if k != "num_hidden_layers"}
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"], cfg["reduced"]) == (10, 40, ["num_hidden_layers"])
    assert cfg["layer_types_run"] == published["config"]["layer_types"][:10] and cfg["layer_types_run"].count("full_attention") == 2
    kinds = cfg["layer_types_run"][: cfg["reference_layers"]]  # a dense layer, an attending layer and a conv layer with experts are compared
    assert cfg["reference_layers"] >= 4 and cfg["num_dense_layers"] == 2 and "full_attention" in kinds[2:] and "conv" in kinds[2:]
    eng = cfg["engine"]
    assert (eng["max_seq_len"], eng["page_size"], eng["prefill_chunk"]) == (8192, 16, 256)
    assert eng["num_pages"] * eng["page_size"] >= eng["num_slots"] * 5120  # every slot at the mix's longest request
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["source"] == published["source_url"] and entry["reduced"] == ["num_hidden_layers"] and len(entry["why"]) <= 200
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "agentturn-saturated", 1) and len(cell["why"]) <= 200
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "serve_tokens_per_s")["workloads"]


def test_conv_costs_against_a_count_by_hand():
    cfg = _load("configs", CONFIG)
    assert costs_conv.layer_kinds(cfg) == (8, 2)
    # in_proj 2048x6144 + out_proj 2048x2048 + taps 3x2048; q, out 2048x2048 each + k, v 2048x512 each + two head norms of 64
    assert costs_conv.conv_mixer_params(cfg) == 12_582_912 + 4_194_304 + 6_144 == 16_783_360
    assert costs_conv.attention_params(cfg) == 2 * 4_194_304 + 2 * 1_048_576 + 128 == 10_485_888
    assert costs_conv.expert_params(cfg) == 9_437_184 and costs_conv.dense_ffn_params(cfg) == 3 * 2048 * 11776 and costs_conv.router_params(cfg) == 2048 * 64 + 64
    assert costs_conv.num_params(cfg) == 5_267_090_176 and costs_conv.num_params({**cfg, "num_hidden_layers": 40}) == 23_843_661_440
    assert costs_conv.cache_bytes_per_position(cfg) == 2 * 2 * 8 * 64 * 2 == 4096  # were all ten layers attention: 20 480
    assert costs_conv.window_bytes_per_slot(cfg) == 8 * 2 * 2048 * 2 == 65_536
    for rows, want in ((80, 63.63), (256, 64.0), (1, 4.0), (0, 0.0)):
        assert costs_conv.experts_touched(cfg, rows) == pytest.approx(want, abs=0.01)
    # a decode step of 80 rows over 170 000 positions: 10.48 GB of weights (every expert touched, the tied matrix once)
    # + 0.70 GB of K/V in two layers + 80 x 2 x 64 KiB of windows
    assert costs_conv.weight_bytes(cfg, 80) == pytest.approx(10.48e9, rel=2e-3)
    step = costs_conv.decode_step_min_bytes(cfg, 80, 170_000)
    assert step == pytest.approx(costs_conv.weight_bytes(cfg, 80) + 170_000 * 4096 + 160 * 65_536) and 13.5e-3 < step / 819e9 < 13.8e-3
    # a token's FLOPs at context 1000: 2 x (8 conv mixers of 16.78 M + 2 attention of 10.49 M + 2 dense of 72.4 M
    # + 8 x (0.13 M router + 4 x 9.44 M)) + 8 conv layers' 8 x 2048 of taps and gates + 2 x 8192 x 1000 of scores and values
    matmul = 8 * 4 * 2048 * 2048 + 2 * (10_485_888 - 128) + 2 * 3 * 2048 * 11776 + 8 * (2048 * 64 + 4 * 9_437_184)
    assert costs_conv.routed_flops_per_token(cfg, 1000) == pytest.approx(2 * matmul + 8 * 8 * 2048 + 2 * 8192 * 1000)
    # a 256-row chunk at context 1000: bytes bind (12.9 ms: the weights, 4 MB of K/V, the slot's windows), not the routed FLOPs (1.6 ms)
    least = costs_conv.prefill_chunk_min_seconds(cfg, 256, 1000, PEAKS)
    assert least == pytest.approx((costs_conv.weight_bytes(cfg, 256) + 1000 * 4096 + 2 * 65_536) / 819e9) and 0.0127 < least < 0.0130
    assert 256 * costs_conv.routed_flops_per_token(cfg, 872) / 197e12 < 0.002


def _view(records, ms_decode=None, ms_prefill=None, config=CONFIG, **counters):
    return {"config": _load("configs", config), "records": records, "peaks": PEAKS, "counters": {"window_s": 10.0, **counters},
            "_engine_programs": {"decode": [ms_decode * 1e-3] if ms_decode else [], "prefill": [ms_prefill * 1e-3] if ms_prefill else []}}


def test_new_readers_known_answers_and_nothing_to_read():
    recs = [{"prompt_len": 1500, "tokens": 500, "sent": -1.0, "done": 12.0, "frames": [(12.0, 500)]} for _ in range(8)]
    live = dict(slots_decode_samples=[79.0, 81.0, 80.0], ctx_positions_live=17_000_000.0, decode_steps=100.0, cache_bytes_per_position=4096.0)
    view = _view(recs, ms_decode=17.0, ms_prefill=17.0, **live)
    least = costs_conv.decode_step_min_bytes(view["config"], 80.0, 170_000.0) / 819e9
    assert conv_decode_hbm_roofline.read(view) == pytest.approx(100 * least / 0.017) and 75 < conv_decode_hbm_roofline.read(view) < 85
    want_share = 100 * 170_000 * 4096 / costs_conv.decode_step_min_bytes(view["config"], 80.0, 170_000.0)
    assert conv_kv_read_share.read(view) == pytest.approx(want_share) and 6.0 < conv_kv_read_share.read(view) < 6.5
    # chunks of 1500-token prompts: five of 256 rows and one of 220; ends 256 .. 1280, 1500
    rows, ends = (5 * 256 + 220) / 6, (sum(256 * (k + 1) for k in range(5)) + 1500) / 6
    want = costs_conv.prefill_chunk_min_seconds(view["config"], rows, ends, PEAKS)
    assert conv_prefill_roofline.read(view) == pytest.approx(100 * want / 0.017) and 70 < conv_prefill_roofline.read(view) < 80
    # a parent without the counters or the programs, another configuration, no samples, no steps: nothing, never an error
    bare = _view(recs)
    assert conv_decode_hbm_roofline.read(bare) is None and conv_prefill_roofline.read(bare) is None and conv_kv_read_share.read(bare) is None
    other = _view(recs, ms_decode=17.0, ms_prefill=17.0, config="moonlight-16b-a3b-l8", **live)
    assert conv_decode_hbm_roofline.read(other) is None and conv_prefill_roofline.read(other) is None and conv_kv_read_share.read(other) is None
    assert conv_decode_hbm_roofline.read(_view(recs, ms_decode=17.0, slots_decode_samples=[80.0])) is None
    assert conv_decode_hbm_roofline.read(_view(recs, ms_decode=17.0, **{**live, "decode_steps": 0.0})) is None
    assert conv_prefill_roofline.read(_view([], ms_prefill=17.0)) is None


def test_reference_check_at_the_configuration_tiny_size():
    from benchmarks.drivers import serve_conv_moe as driver

    cfg = bench_run.merge_tiny(_load("configs", CONFIG))
    lcfg = driver.conv_config(cfg)
    assert (lcfg.n_experts, lcfg.n_experts_per_tok, lcfg.n_dense_layers, lcfg.conv_kernel, lcfg.layer_kinds) == (8, 2, 2, 3, ("conv", "conv", "attn", "conv", "attn", "conv"))
    for key, bad in (("conv_bias", True), ("use_expert_bias", False), ("tie_embedding", False), ("rope_parameters", {"rope_theta": 1e6, "rope_type": "yarn"}), ("num_hidden_layers", 7)):
        with pytest.raises(ValueError):  # what the program's block cannot compute is refused, not ignored
            driver.conv_config({**cfg, key: bad})
    out = driver._reference_check_in_worker(cfg, 3)  # bf16, as the chip runs it; every departure tried inside
    assert out["ok"] and out["as_published_ok"] and out["departures_passed"] == [] and out["chunks"] == 2, out
    assert all(not out[which]["ok"] for which in driver.DEPARTURES)
    assert (out["layer_kinds"], out["kv_layers"], out["window_shape"]) == ("ccacac", 2, [4, 2, 2, 64]) and out["prompt_len"] > cfg["engine"]["prefill_chunk"]
    control = driver._reference_check_in_worker(cfg, 3, control=True)  # the limits' second reading: fp8 weights against the weights themselves
    assert not control["ok"] and control["k_rel_err"] > 3 * out["k_rel_err"], control


def test_a_program_without_the_model_is_refused_before_anything_starts(monkeypatch):
    """The parent commit under this PR's benchmark files: it has no
    ``ray_tpu/models/lfm2.py``, and the driver must fail at once."""
    from benchmarks.drivers import serve_conv_moe, serve_mla_moe, serve_moe

    monkeypatch.setitem(sys.modules, "ray_tpu.models.lfm2", None)  # what importing a missing module does
    started = []
    monkeypatch.setattr(serve_moe, "run", lambda ctx: started.append(ctx))
    monkeypatch.setattr(serve_mla_moe, "run", lambda ctx: started.append(ctx))
    ctx = bench_run.Context({}, _load("configs", CONFIG), {}, 1, 45.0, False, False, "", "")
    with pytest.raises(ImportError):
        serve_conv_moe.run(ctx)
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
    assert len(listed) == 13 and all(n.endswith(".lfm2-sat") for n in listed)
    # the device-program readers find no XLA Modules line on the CPU and are left out there, as in the older cells
    on_cpu = {n for n in listed if not n.startswith(("decode_program_ms", "prefill_program_ms", "conv_decode", "conv_prefill"))}
    out, detail = _rehearse(1)
    assert on_cpu <= set(out["metrics"]), on_cpu - set(out["metrics"])
    ref = detail["notes"]["reference_check"]  # this kind's check ran, with every departure
    assert ref["ok"] and (ref["layer_kinds"], ref["window_shape"]) == ("ccacac", [4, 2, 2, 64]) and ref["departures_passed"] == []
    c = detail["counters"]  # this kind's replica answered, and the expert kind's client kept its replies
    assert c["cache_bytes_per_position"] == 2 * 2 * 2 * 16 * 2 and c["state_bytes_per_slot"] == 4 * 2 * 64 * 2 and c["state_bytes"] == 4 * c["state_bytes_per_slot"]
    assert c["ctx_positions_live"] > 0 and c["decode_steps"] > 0 and c["moe_assignments"] > 0 and c["state_resets"] > 0
    assert 0 < out["metrics"]["conv_kv_read_share.lfm2-sat"]["value"] < 100
    assert out["metrics"]["engine_slots_active_unstalled.lfm2-sat"]["value"] > 3.0
    st = detail["notes"]["stats_end"]
    assert (st["compile_prefill"], st["compile_decode"]) == (1, 1)


def test_the_tiny_untraced_rehearsal_reports_a_throughput_and_set_up():
    out, detail = _rehearse(0)
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0 and detail["counters"]["requests_completed_in_window"] > 0


def test_the_kind_hands_on_what_the_latent_kind_found_and_only_adds_its_counters(monkeypatch):
    """``run`` composes ``serve_mla_moe.run`` (and through it ``serve_moe.run``):
    a snapshot asked late stays that run's problem; the live positions of the
    decode steps come from there, by import; this kind adds what the pool keeps
    a slot."""
    import types

    from benchmarks.drivers import serve_conv_moe as driver
    from benchmarks.drivers import serve_mla_moe, serve_moe

    late = f"no engine_stats reply with routing counters within {serve_moe.SNAPSHOT_SLACK_S} s of each end of the window"
    reply = lambda steps: {"ctx_positions_live": 2000.0 * steps, "decode_steps": float(steps), "cache_bytes_per_position": 4096.0,  # noqa: E731
                           "state_bytes": 6291456.0, "state_bytes_per_slot": 65536.0, "state_resets": float(steps // 100)}
    found = {"problems": [late], "correct": False, "window_epoch": 1000.0, "counters": {}, "notes": {}}
    monkeypatch.setattr(serve_moe, "run", lambda ctx: found)
    monkeypatch.setattr(serve_moe._Client, "stats_log", [(1000.1, reply(10)), (1015.2, reply(400)), (1018.1, reply(500)), (1045.64, reply(1200))])
    ctx = types.SimpleNamespace(config=_load("configs", CONFIG), seconds=45.0, traffic={"trace_seconds": 3.0}, trace=1)
    saved = (serve_mla_moe.mla_config, serve_mla_moe.reference_check)
    out = driver.run(ctx)
    assert (serve_mla_moe.mla_config, serve_mla_moe.reference_check) == saved  # put back
    assert not out["correct"] and out["problems"] == [late]
    assert out["counters"] == {"ctx_positions_live": 200000.0, "decode_steps": 100.0, "cache_bytes_per_position": 4096.0,
                               "state_resets": 12.0, "state_bytes": 6291456.0, "state_bytes_per_slot": 65536.0}
