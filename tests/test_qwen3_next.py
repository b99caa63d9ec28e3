"""Qwen3-Next on the normal path (models/qwen3_next.py, parallel/moe.py's
routed layer told which experts it holds, serve/llm.py, the engine with
per-slot state beside the paged cache) against the plain float32 reference
(benchmarks/reference/qwen3_next_ref.py), at a tiny size on the CPU.  The
comparison is the one the benchmark's traced run makes on the chip
(benchmarks/drivers/serve_qwen3_next.py compare)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.drivers import serve_qwen3_next as driver  # noqa: E402
from benchmarks.reference import qwen3_next_ref as ref_mod  # noqa: E402
from ray_tpu.models import qwen3_next  # noqa: E402
from ray_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextModel  # noqa: E402
from ray_tpu.parallel import moe  # noqa: E402
from ray_tpu.serve.llm import ShardedLLM  # noqa: E402

PAGE, CHUNK = 8, 64
PROMPT = np.random.default_rng(5).integers(1, 250, 2 * CHUNK + CHUNK // 3 + 5).astype(np.int32)  # three chunks, the third ragged


def tiny(dtype, **kw):
    """One period (l l l f) at 64 wide; experts 4..7 of the router's 16 held."""
    base = dict(
        vocab_size=250, dim=64, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=32, hidden_dim=32, shared_hidden_dim=32,
        n_experts=4, n_routed_experts=16, expert_offset=4, n_experts_per_tok=3, lin_key_heads=2, lin_value_heads=4,
        lin_key_dim=16, lin_value_dim=16, max_seq_len=256, compute_dtype=dtype, param_dtype=dtype,
    )
    return Qwen3NextConfig(**{**base, **kw})


def crafted(cfg):
    """``Qwen3NextModel.init``'s tree with the expert matrices x3 and the
    router x10 (at 64 wide and N(0, 0.02) the experts hardly move the
    residual stream and every choice is a near tie), and with norm scales
    that are not their initial zeros and ones."""
    p = Qwen3NextModel(dataclasses.replace(cfg, param_dtype=jnp.float32)).init(jax.random.PRNGKey(0))
    k = iter(jax.random.split(jax.random.PRNGKey(7), 8))
    m, f, lin = dict(p["moe"]), dict(p["full"]), dict(p["linear"])
    for name in ("w_gate", "w_up", "w_down", "ws_gate", "ws_up", "ws_down"):
        m[name] = m[name] * 3.0
    m["router"], m["shared_gate"] = m["router"] * 10.0, m["shared_gate"] * 20.0
    for tree, names in ((m, ("attn_norm", "ffn_norm")), (f, ("q_norm", "k_norm")), (lin, ("out_norm",))):
        for name in names:
            tree[name] = tree[name] + 0.2 * jax.random.normal(next(k), tree[name].shape)
    out = {**p, "moe": m, "full": f, "linear": lin, "final_norm": p["final_norm"] + 0.1}
    return jax.tree.map(lambda a: a.astype(cfg.param_dtype), out)


# ------------------------------------------------------------ the recurrence


def _rule_inputs(T, H=4, Dk=16, Dv=16, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    k = f(T, H, Dk)
    return (f(T, H, Dk) * Dk**-0.5, k / jnp.linalg.norm(k, axis=-1, keepdims=True), f(T, H, Dv),
            -jnp.asarray(rng.uniform(0, 1, (T, H)), jnp.float32), jnp.asarray(rng.uniform(0, 1, (T, H)), jnp.float32))


@pytest.mark.parametrize("T", [64, 192, 100, 201])
def test_the_chunked_gated_delta_rule_equals_the_per_token_recurrence(T):
    """Lengths that are and are not multiples of the block of 64: a ragged
    tail is padded with rows of g = 0 and beta = 0, as the mixer pads a
    chunk, and those rows must leave the state bit for bit as it was."""
    q, k, v, g, beta = _rule_inputs(T)
    S0 = jnp.asarray(np.random.default_rng(1).normal(size=(4, 16, 16)), jnp.float32)
    want_o, want_S = [], S0
    for t in range(T):
        o, want_S = qwen3_next.gated_delta_step(q[t], k[t], v[t], g[t], beta[t], want_S)
        want_o.append(o)
    padded = -(-T // 64) * 64
    pad = lambda a: jnp.zeros((padded, *a.shape[1:]), a.dtype).at[:T].set(a)  # noqa: E731
    got_o, got_S = jax.jit(qwen3_next.gated_delta_chunked)(pad(q), pad(k), pad(v), pad(g), pad(beta), S0)
    np.testing.assert_allclose(np.asarray(got_o[:T]), np.asarray(jnp.stack(want_o)), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_S), np.asarray(want_S), atol=2e-5)
    # the reference's scan from a zero state is the same rule
    ref_o, ref_S = ref_mod.delta_rule(q, k, v, g, beta)
    got_o, got_S = jax.jit(qwen3_next.gated_delta_chunked)(pad(q), pad(k), pad(v), pad(g), pad(beta), jnp.zeros_like(S0))
    np.testing.assert_allclose(np.asarray(got_S), np.asarray(ref_S), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_o[:T]), np.asarray(ref_o), atol=2e-5)


def test_rows_that_are_not_valid_leave_the_state_bit_for_bit():
    q, k, v, g, beta = _rule_inputs(64)
    S0 = jnp.asarray(np.random.default_rng(2).normal(size=(4, 16, 16)), jnp.float32)
    _, S = qwen3_next.gated_delta_chunked(q, k, v, jnp.zeros_like(g), jnp.zeros_like(beta), S0)
    assert np.array_equal(np.asarray(S), np.asarray(S0))
    _, S = qwen3_next.gated_delta_step(q[0], k[0], v[0], jnp.zeros_like(g[0]), jnp.zeros_like(beta[0]), S0)
    assert np.array_equal(np.asarray(S), np.asarray(S0))


# --------------------------------------------------- the program and the reference


def test_float32_program_matches_the_reference_through_the_pool():
    """Prefill in three engine chunks (the third ragged) on a slot that was
    used before, then eight decode steps, against the reference's one full
    forward: logits, the full layer's K/V, every linear layer's state and
    conv window, the router, the counter -- all within 1e-4."""
    cfg = tiny(jnp.float32)
    llm = ShardedLLM(cfg, tp=1, init=crafted(cfg))
    tight = dict(kv_tol=1e-4, kv_max_tol=1e-4, logit_tol=1e-4, state_tol=1e-4, state_max_tol=1e-4, window_tol=1e-4, margin=1e-5)
    out = driver.compare(llm, PROMPT, page=PAGE, chunk=CHUNK, **tight)
    assert out["ok"], out
    assert out["layer_kinds"] == "lllf" and out["chunks"] == 3 and out["decode_steps"] == 8
    assert out["routing_agreement"] == 1.0 and not out["idle_slot_touched"] and out["state_dtype"] == "float32"
    assert out["moe_load_total"] == (len(PROMPT) + 8 + CHUNK // 2) * 4 * 3 and out["moe_load_miscount"] == 0
    assert 0.15 < out["moe_held_share"] < 0.4  # 4 of 16 experts held


def _unrenormalised(fn):
    def layer(*args, **kw):
        return fn(*args, **{**kw, "renormalize": False})

    return layer


# Tolerances: the chip's (benchmarks/drivers/serve_qwen3_next.py and
# drivers/serve.py).  A bf16 program is inside them; a state rounded to bf16
# between calls and a bf16 router softmax are refused by the two checks that
# isolate them (RULE_TOL, ROUTER_TOL), top-k weights not renormalised and a
# missing output gate by the logits and the state downstream.
@pytest.mark.parametrize("variant", ["as_published", "bf16_state", "bf16_router", "not_renormalised", "no_partial_rotary"])
def test_bf16_program_is_inside_the_chip_tolerances_and_each_departure_is_not(variant, monkeypatch):
    cfg = tiny(jnp.bfloat16)
    params = crafted(cfg)
    program_cfg = cfg
    if variant == "not_renormalised":
        monkeypatch.setattr(moe, "dropless_moe_ffn", _unrenormalised(moe.dropless_moe_ffn))
    elif variant == "no_partial_rotary":
        program_cfg = dataclasses.replace(cfg, partial_rotary_factor=1.0)
    llm = ShardedLLM(program_cfg, tp=1, init=params)
    if variant in ("bf16_state", "bf16_router"):
        with driver.departure(variant):
            out = driver.compare(llm, PROMPT, page=PAGE, chunk=CHUNK)
    else:
        if variant == "no_partial_rotary":  # the reference keeps the published factor
            llm.cfg = cfg
        out = driver.compare(llm, PROMPT, page=PAGE, chunk=CHUNK)
    assert out["ok"] == (variant == "as_published"), out
    if variant == "as_published":
        assert out["routing_flips_above_margin"] == 0 and out["rule_alone_err"] < 2e-5 and out["router_weight_err"] < 1e-5
        assert out["k_rel_err"] < 0.01 and out["state_rel_err"] < 0.015 and out["window_rel_err"] < 0.01
    elif variant == "bf16_state":
        assert out["rule_alone_err"] > 5 * driver.RULE_TOL and out["rule_alone_max_err"] > driver.RULE_MAX_TOL
    elif variant == "bf16_router":
        assert out["router_weight_err"] > 10 * driver.ROUTER_TOL


def test_the_departures_are_seen_by_the_two_checks_that_isolate_them():
    """What a traced run of the cell does: the program as published is ok,
    and each departure tried on the recurrence alone and the router alone
    comes out not ok."""
    cfg = tiny(jnp.bfloat16)
    llm = ShardedLLM(cfg, tp=1, init=crafted(cfg))
    out = driver.compare(llm, PROMPT, page=PAGE, chunk=CHUNK, departures=("bf16_state", "bf16_router"))
    assert out["ok"] and not out["bf16_state"]["ok"] and not out["bf16_router"]["ok"], out
    assert out["bf16_state"]["router_weight_err"] < 1e-5 and out["bf16_router"]["rule_alone_err"] < 2e-5  # each sees its own departure only


def test_partial_rotary_and_the_zero_centred_norm_equal_the_reference():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(11, 4, 32)), jnp.float32)
    got = qwen3_next._partial_rope(x[None], jnp.arange(11)[None], 1e7, 8)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref_mod._rope_partial(x, 1e7, 8)), atol=1e-6)
    assert np.array_equal(np.asarray(got[..., 8:]), np.asarray(x[..., 8:]))  # three quarters of a head pass unrotated
    w = jnp.asarray(rng.normal(size=(32,)) * 0.2, jnp.float32)
    np.testing.assert_allclose(np.asarray(qwen3_next._zrms_norm(x, w, 1e-6)), np.asarray(ref_mod._norm(x, w, 1e-6)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(qwen3_next._zrms_norm(x, jnp.zeros(32), 1e-6)), np.asarray(x / jnp.sqrt((x**2).mean(-1, keepdims=True) + 1e-6)), atol=1e-6)


# ------------------------------------------------------------ the routed layer


@pytest.mark.parametrize("renormalize", [True, False])
def test_the_four_shares_of_the_experts_add_up_to_the_uncut_layer(renormalize):
    """Offsets 0, 4, 8, 12 of 16 experts: the four holders' parts add up to
    the reference's layer over all 16, renormalised or not; a row none of
    whose choices is held gets exactly zero from that holder."""
    rng = np.random.default_rng(4)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    T, E, H, X, K = 24, 32, 16, 16, 3
    h, router, wg, wu, wd = f(T, E), f(E, X), f(X, E, H) * 0.3, f(X, E, H) * 0.3, f(X, H, E) * 0.3
    probs, chosen = ref_mod.route(h, router, K)
    with jax.default_matmul_precision("highest"):
        want = ref_mod.expert_ffn(h, probs, chosen, wg, wu, wd, offset=0, renormalize=renormalize)
    parts = []
    for off in (0, 4, 8, 12):
        held = slice(off, off + 4)
        y, got_chosen = moe.dropless_moe_ffn(h, router, wg[held], wu[held], wd[held], top_k=K, renormalize=renormalize, expert_offset=off)
        assert np.array_equal(np.asarray(got_chosen), np.asarray(chosen))  # every holder routes over all 16
        missed = ~((np.asarray(chosen) >= off) & (np.asarray(chosen) < off + 4)).any(-1)
        assert missed.any() and not np.asarray(y)[missed].any()
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(np.asarray(y), np.asarray(ref_mod.expert_ffn(h, probs, chosen, wg[held], wu[held], wd[held], offset=off, renormalize=renormalize)), atol=1e-4)
        parts.append(y)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want), atol=2e-4)
    whole, _ = moe.dropless_moe_ffn(h, router, wg, wu, wd, top_k=K, renormalize=renormalize)  # all held: OLMoE's call
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=2e-4)


def test_published_sizes():
    cut = Qwen3NextConfig(n_layers=8, n_experts=128, vocab_size=37984)
    assert cut.num_params() == 3_667_251_328 and cut.layer_kinds == ("linear", "linear", "linear", "full") * 2
    whole = Qwen3NextConfig()
    assert whole.num_params() == 79_674_391_296 and whole.layer_kinds.count("full") == 12
    assert 3.5e9 < whole.active_params_per_token() < 4.0e9  # "A3B": 3 B beside embedding and head
    assert (cut.head_dim, cut.rotary_dim, cut.conv_dim, cut.padded_vocab) == (256, 64, 8192, 38016)
    # the tree holds what the count says (embedding and head padded to 128 rows)
    cfg = tiny(jnp.float32)
    leaves = jax.tree.leaves(jax.eval_shape(Qwen3NextModel(cfg).init, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in leaves) == cfg.num_params() + 2 * (cfg.padded_vocab - cfg.vocab_size) * cfg.dim
    with pytest.raises(ValueError):
        Qwen3NextConfig(n_experts=128, expert_offset=449)  # 449 + 128 experts are not among the router's 512


def test_a_chunk_that_is_not_a_multiple_of_the_block_is_refused():
    cfg = tiny(jnp.float32)
    llm = ShardedLLM(cfg, tp=1)
    programs = llm.engine_programs(num_pages=8, page_size=PAGE, num_slots=1)
    with pytest.raises(ValueError, match="multiple"):
        programs["prefill"](llm.params, programs["init"](), np.zeros(8, np.int32), np.zeros(48, np.int32), np.int32(0), np.int32(5), np.int32(0))
    with pytest.raises(ValueError, match="slots"):
        llm.engine_programs(num_pages=8, page_size=PAGE)["init"]()


# ------------------------------------------------------------------ the engine


def _ref_greedy(cfg, params, prompt, n_new, buf=192):
    """Greedy tokens from the reference's full forward, no cache: the
    sequence lives in a buffer of one length (a causal forward's logits do
    not see what follows a position)."""
    fwd = jax.jit(lambda p, t: ref_mod.forward(p, t, **driver.reference_kwargs(cfg)).logits)
    seq = np.zeros(buf, np.int32)
    seq[: len(prompt)] = prompt
    for i in range(len(prompt), len(prompt) + n_new):
        seq[i] = int(np.argmax(np.asarray(fwd(params, jnp.asarray(seq))[i - 1])[: cfg.vocab_size]))
    return seq[len(prompt) : len(prompt) + n_new].tolist()


@pytest.fixture(scope="module")
def served():
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = tiny(jnp.float32)
    llm = ShardedLLM(cfg, tp=1, init=crafted(cfg))
    eng = InferenceEngine(llm, EngineConfig(num_slots=3, page_size=PAGE, max_seq_len=192, prefill_chunk=CHUNK, max_new_tokens=5, gauge_period_s=0.0), deployment="t")
    yield cfg, llm, eng
    eng.shutdown()


PROMPTS = [[5, 7, 9], list(PROMPT[:70]), list(PROMPT[:150])]  # one, two and three chunks


def test_the_engine_serves_a_fleet_of_mixed_lengths_on_one_shape_each(served):
    cfg, llm, eng = served
    outs = [r.sink.result(timeout=300) for r in [eng.submit(list(map(int, p)), 5) for p in PROMPTS]]
    for p, o in zip(PROMPTS, outs):
        assert o == _ref_greedy(cfg, llm.params, p, 5)  # the reference: no cache, no chunks, no pool
    assert eng.compile_stats() == {"prefill": 1, "decode": 1}


def test_a_row_is_the_same_alone_and_among_others_and_a_reused_slot_starts_clean(served):
    """Each prompt again, alone, on slot 0 -- which the request before it
    left with a state and a window of its own: the same tokens as in the
    fleet, and as a fresh engine's."""
    cfg, llm, eng = served
    want = [_ref_greedy(cfg, llm.params, p, 5) for p in PROMPTS]
    resets = eng.stats()["state_resets"]
    for p, w in zip(PROMPTS[::-1], want[::-1]):
        req = eng.submit(list(map(int, p)), 5)
        assert req.sink.result(timeout=300) == w
    assert eng.stats()["state_resets"] == resets + 3 and eng.compile_stats() == {"prefill": 1, "decode": 1}
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    fresh = InferenceEngine(llm, EngineConfig(num_slots=1, page_size=PAGE, max_seq_len=192, prefill_chunk=CHUNK, max_new_tokens=5), deployment="f")
    try:
        assert fresh.submit(list(map(int, PROMPTS[1])), 5).sink.result(timeout=300) == want[1]
    finally:
        fresh.shutdown()


def test_defrag_moves_pages_and_leaves_state_alone(served):
    cfg, llm, eng = served
    want = _ref_greedy(cfg, llm.params, PROMPTS[2], 5)
    first = eng.submit(list(map(int, PROMPTS[0])), 5)  # takes the lowest pages, then frees them
    second = eng.submit(list(map(int, PROMPTS[2])), 5)
    first.sink.result(timeout=300)
    moved = eng.defrag()
    assert second.sink.result(timeout=300) == want and "moves" in moved
    # at rest: a compaction copies pool members 0 and 1 and hands the others on as they are
    before = [np.asarray(a) for a in eng._pages[2:]]
    eng.defrag()
    assert all(np.array_equal(a, np.asarray(b)) for a, b in zip(before, eng._pages[2:]))


def test_engine_stats_carry_the_state_and_held_share_counters(served):
    import time

    cfg, llm, eng = served
    eng.submit([3, 4, 5, 6], 3).sink.result(timeout=300)
    eng._wake.set()
    time.sleep(0.3)  # an idle tick publishes
    st = eng.stats()
    assert st["state_bytes"] == 3 * 3 * (4 * 16 * 16 * 4 + 3 * 128 * 4)  # slots x linear layers x (state + window), float32 here
    assert st["state_resets"] >= 1 and len(st["moe_expert_load"]) == cfg.n_experts
    assert st["moe_assignments_held"] == sum(st["moe_expert_load"]) == st["moe_assignments"]
    assert st["moe_assignments_seen"] % (cfg.n_layers * cfg.n_experts_per_tok) == 0  # whole rows
    assert 0.1 < st["moe_assignments_held"] / st["moe_assignments_seen"] < 0.5


# ------------------------------------ the decode step reads the touched experts only


def _stats_after_a_tick(eng):
    import time

    eng._wake.set()
    time.sleep(0.3)  # an idle tick reads the pool's counters
    return eng.stats()


def test_engine_stats_count_the_expert_reads_of_the_decode_steps():
    """Two slots x top 3 of 16: the decode step takes the routed layer's
    touched form, and ``moe_expert_reads`` grows by the distinct (layer, held
    expert) pairs that the reference's routing gives for the rows a step
    decodes -- one request alone, so one row a step; the chunks (64 rows: the
    masked form) and the idle slot add nothing."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = tiny(jnp.float32)
    assert moe.reads_touched_experts_only(2, cfg.n_experts_per_tok, cfg.n_routed_experts) and not moe.reads_touched_experts_only(CHUNK, cfg.n_experts_per_tok, cfg.n_routed_experts)
    llm = ShardedLLM(cfg, tp=1, init=crafted(cfg))
    eng = InferenceEngine(llm, EngineConfig(num_slots=2, page_size=PAGE, max_seq_len=192, prefill_chunk=CHUNK, max_new_tokens=8, gauge_period_s=0.0), deployment="r")
    try:
        assert eng.stats()["moe_expert_reads"] == 0.0
        want_reads = want_steps = 0
        for prompt, n_new in ((PROMPTS[1], 6), (PROMPTS[0], 4)):
            out = eng.submit(list(map(int, prompt)), n_new).sink.result(timeout=300)
            seq = np.zeros(192, np.int32)
            seq[: len(prompt) + n_new] = list(prompt) + out
            chosen = np.asarray(jax.jit(lambda p, t: ref_mod.forward(p, t, **driver.reference_kwargs(cfg)).chosen)(llm.params, jnp.asarray(seq)))  # [L, S, K]
            fed = chosen[:, len(prompt) : len(prompt) + n_new - 1]  # a decode step feeds every generated token but the last
            held = (fed >= cfg.expert_offset) & (fed < cfg.expert_offset + cfg.n_experts)
            want_reads += sum(len(set(row[keep])) for layer, kept in zip(fed, held) for row, keep in zip(layer, kept))
            want_steps += n_new - 1
        st = _stats_after_a_tick(eng)
        assert st["decode_steps"] == want_steps and st["rows_discarded"] == 0
        assert st["moe_expert_reads"] == want_reads > 0
        assert st["moe_expert_reads"] / (st["decode_steps"] * cfg.n_layers * cfg.n_experts) < 0.5  # one row: at most 3 of the 4 held a layer
        assert eng.compile_stats() == {"prefill": 1, "decode": 1}
    finally:
        eng.shutdown()


def test_an_engine_whose_decode_step_takes_the_masked_form_reports_no_expert_reads(served):
    """OLMoE's shape of call (slots x top_k over the router's experts): its
    pool and programs carry no such count.  And the 3-slot Qwen3-Next engine
    above (2 * 3 * 3 > 16: masked) carries one that stays at zero."""
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    ocfg = LlamaConfig.tiny(compute_dtype=jnp.float32, n_experts=4, n_experts_per_tok=2)
    assert not moe.reads_touched_experts_only(3, ocfg.n_experts_per_tok, ocfg.n_experts)
    eng = InferenceEngine(ShardedLLM(ocfg, tp=1), EngineConfig(num_slots=3, page_size=PAGE, max_seq_len=64, prefill_chunk=16, max_new_tokens=4, gauge_period_s=0.0), deployment="o")
    try:
        eng.submit([5, 7, 9], 4).sink.result(timeout=300)
        st = _stats_after_a_tick(eng)
        assert st["moe_assignments"] > 0 and "moe_expert_reads" not in st
    finally:
        eng.shutdown()
    _, _, hybrid = served
    hybrid.submit([3, 4, 5], 3).sink.result(timeout=300)
    st = _stats_after_a_tick(hybrid)
    assert st["decode_steps"] > 0 and st["moe_expert_reads"] == 0.0
