"""Jamba on the normal path (models/jamba.py, ops/selective_scan.py,
serve/llm.py, the engine with per-slot state beside the paged cache and a
pool whose members the model names) against the plain float32 reference
(benchmarks/reference/jamba_ref.py), at a tiny size on the CPU.  The
comparison is the one the benchmark's traced run makes on the chip
(benchmarks/drivers/serve_jamba.py compare)."""

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.drivers import serve_jamba as driver  # noqa: E402
from benchmarks.reference import jamba_ref as ref_mod  # noqa: E402
from ray_tpu.models import jamba  # noqa: E402
from ray_tpu.models.jamba import JambaConfig, JambaModel  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.ops import selective_scan as ssm  # noqa: E402
from ray_tpu.serve.llm import ShardedLLM  # noqa: E402

PAGE, CHUNK = 8, 32
PROMPT = np.random.default_rng(5).integers(1, 250, 2 * CHUNK + CHUNK // 3 + 5).astype(np.int32)  # three chunks, the third ragged


def tiny(dtype, **kw):
    """Mamba Mamba attention Mamba at 64 wide (d_inner 128: one tile of channels)."""
    base = dict(vocab_size=250, dim=64, n_layers=4, n_heads=4, n_kv_heads=1, hidden_dim=128, max_seq_len=192, attn_layer_period=4,
                attn_layer_offset=2, d_state=8, dt_rank=4, compute_dtype=dtype, param_dtype=dtype)
    return JambaConfig(**{**base, **kw})


def crafted(cfg):
    """``JambaModel.init``'s tree with the matrices x4 (at 64 wide and N(0,
    0.02) the mixers hardly move the residual stream), and with norm scales,
    A_log and D that are not their initial ones, constants and channel-alike."""
    p = JambaModel(dataclasses.replace(cfg, param_dtype=jnp.float32)).init(jax.random.PRNGKey(0))
    k = iter(jax.random.split(jax.random.PRNGKey(7), 16))
    m, a, f = dict(p["mamba"]), dict(p["attn"]), dict(p["ffn"])
    for tree, names in ((m, ("w_in", "w_x", "w_out")), (a, ("wq", "wk", "wv", "wo")), (f, ("w_gate", "w_up", "w_down"))):
        for name in names:
            tree[name] = tree[name] * 4.0
    for tree, names in ((m, ("dt_norm", "b_norm", "c_norm", "A_log", "D")), (f, ("attn_norm", "ffn_norm"))):
        for name in names:
            tree[name] = tree[name] + 0.2 * jax.random.normal(next(k), tree[name].shape)
    out = {**p, "mamba": m, "attn": a, "ffn": f, "final_norm": p["final_norm"] + 0.1, "tok_emb": p["tok_emb"] * 4.0}
    return jax.tree.map(lambda x: x.astype(cfg.param_dtype), out)


# ------------------------------------------------------------ the recurrence


def _scan_inputs(B, T, R=16, N=8, layers=3, slots=5, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    dt = jnp.asarray(rng.uniform(0.001, 0.2, (B, T, R, 128)), jnp.float32)
    return f(B, T, R, 128), dt, f(B, T, N), f(B, T, N), -jnp.exp(f(N, R, 128) * 0.5), f(R, 128), f(layers, slots, N, R, 128)


def _per_token(u, dt, Bm, Cm, A, D, h):
    """The recurrence written out, one row of one sequence at a time."""
    ys = []
    for t in range(u.shape[0]):
        h = jnp.exp(dt[t][None] * A) * h + (dt[t] * u[t])[None] * Bm[t][:, None, None]
        ys.append((h * Cm[t][:, None, None]).sum(0) + D * u[t])
    return jnp.stack(ys), h


@pytest.mark.parametrize("impl", ["interpret", "plain"])
@pytest.mark.parametrize("T", [32, 20, 7])
def test_the_chunk_scan_equals_the_per_token_recurrence(T, impl):
    """A chunk of 32 rows of which T are valid, from a state that is not zero:
    the kernel (in the Pallas interpreter; two tiles of 8 x 128 channels) and
    its plain twin; the padded rows have dt = 0 and must leave the state bit
    for bit where the last valid row left it."""
    u, dt, Bm, Cm, A, D, state = _scan_inputs(1, 32)
    dt = dt.at[:, T:].set(0.0)
    want_y, want_h = _per_token(u[0, :T], dt[0, :T], Bm[0, :T], Cm[0, :T], A, D, state[1, 3])
    got_y, got = jax.jit(lambda *a: ssm.selective_scan(*a, 1, jnp.int32(3), impl=impl))(u, dt, Bm, Cm, A, D, state)
    np.testing.assert_allclose(np.asarray(got_y[0, :T]), np.asarray(want_y), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got[1, 3]), np.asarray(want_h), atol=2e-5)
    # nothing but the call's layer and slot moved
    untouched = np.ones(state.shape[:2], bool)
    untouched[1, 3] = False
    assert np.array_equal(np.asarray(got)[untouched], np.asarray(state)[untouched])
    # the same rows without the padded tail: the padded rows changed no bit of the state
    _, short = jax.jit(lambda *a: ssm.selective_scan(*a, 1, jnp.int32(3), impl=impl))(u[:, :T], dt[:, :T], Bm[:, :T], Cm[:, :T], A, D, state)
    assert np.array_equal(np.asarray(got[1, 3]), np.asarray(short[1, 3]))
    # the reference's scan from a zero state is the same rule (a fresh row starts from zero whatever the slot held)
    flat = lambda a: a.reshape(*a.shape[:-2], -1)  # noqa: E731
    ref_y, ref_h = ref_mod.selective_scan(flat(u[0, :T]), flat(dt[0, :T]), Bm[0, :T], Cm[0, :T], flat(A), flat(D))
    got_y, got = jax.jit(lambda *a: ssm.selective_scan(*a, 1, jnp.int32(3), jnp.asarray([True]), impl=impl))(u, dt, Bm, Cm, A, D, state)
    np.testing.assert_allclose(np.asarray(flat(got_y[0, :T])), np.asarray(ref_y), atol=2e-5)
    np.testing.assert_allclose(np.asarray(flat(got[1, 3])), np.asarray(ref_h), atol=2e-5)


@pytest.mark.parametrize("impl", ["interpret", "plain"])
def test_a_decode_step_of_the_scan_moves_every_slot_once_and_idle_rows_not_at_all(impl):
    u, dt, Bm, Cm, A, D, state = _scan_inputs(8, 1, slots=8)
    dt = dt.at[2].set(0.0).at[5].set(0.0)  # two rows that are not valid
    fresh = jnp.asarray([False, True] + [False] * 6)
    y, got = jax.jit(lambda *a: ssm.selective_scan(*a, 2, None, fresh, impl=impl))(u, dt, Bm, Cm, A, D, state)
    for b in range(8):
        want_y, want_h = _per_token(u[b], dt[b], Bm[b], Cm[b], A, D, jnp.zeros_like(state[2, b]) if b == 1 else state[2, b])
        np.testing.assert_allclose(np.asarray(y[b]), np.asarray(want_y), atol=2e-5)
        np.testing.assert_allclose(np.asarray(got[2, b]), np.asarray(want_h), atol=2e-5)
    assert np.array_equal(np.asarray(got[2, [2, 5]]), np.asarray(state[2, [2, 5]]))  # bit for bit
    assert np.array_equal(np.asarray(got[:2]), np.asarray(state[:2]))
    with pytest.raises(ValueError, match="rows"):
        ssm.selective_scan(u[:3], dt[:3], Bm[:3], Cm[:3], A, D, state, 2, impl=impl)


def test_rows_that_are_not_valid_leave_state_and_window_bit_for_bit():
    """Through the two programs: a decode step with no active slot, and a
    chunk with no valid row, hand the whole pool back as it was."""
    cfg = tiny(jnp.float32)
    llm = ShardedLLM(cfg, tp=1, init=crafted(cfg))
    programs = llm.engine_programs(num_pages=24, page_size=PAGE, num_slots=2)
    tables = np.full((2, 12), -1, np.int32)
    tables[1] = np.arange(12)[::-1]
    pool = programs["init"]()
    toks = np.zeros(CHUNK, np.int32)
    toks[:20] = PROMPT[:20]
    _, pool = programs["prefill"](llm.params, pool, np.ascontiguousarray(tables[1]), toks, np.int32(0), np.int32(20), np.int32(1))
    before = [np.asarray(a) for a in pool]
    assert before[2][:, 1].any() and before[3][:, 1].any() and not before[2][:, 0].any()
    _, pool = programs["decode"](llm.params, pool, tables, np.zeros(2, np.int32), np.asarray([0, 20], np.int32), np.zeros(2, bool))
    _, pool = programs["prefill"](llm.params, pool, np.ascontiguousarray(tables[1]), toks, np.int32(20), np.int32(0), np.int32(1))
    assert all(np.array_equal(a, np.asarray(b)) for a, b in zip(before, pool))


# --------------------------------------------------- the program and the reference

TIGHT = dict(kv_tol=1e-4, kv_max_tol=1e-4, logit_tol=1e-4, state_tol=1e-4, state_max_tol=1e-4, window_tol=1e-4, rule_tol=1e-5, rule_max_tol=1e-4)


@pytest.mark.parametrize("impl", ["plain", "interpret"])
def test_float32_program_matches_the_reference_through_the_pool(impl, monkeypatch):
    """Prefill in three engine chunks (the third ragged) on a slot that was
    used before, then eight decode steps, against the reference's one full
    forward: logits, the attending layer's K/V, every Mamba layer's state and
    conv window, the recurrence alone -- all within 1e-4; with the scan's
    plain form (what the CPU runs) and with its kernel in the interpreter."""
    if impl == "interpret":
        monkeypatch.setattr(ssm, "selective_scan", functools.partial(ssm.selective_scan, impl="interpret"))
    cfg = tiny(jnp.float32)
    llm = ShardedLLM(cfg, tp=1, init=crafted(cfg))
    out = driver.compare(llm, PROMPT, page=PAGE, chunk=CHUNK, **TIGHT)
    assert out["ok"], out
    assert out["layer_kinds"] == "mmam" and out["chunks"] == 3 and out["decode_steps"] == 8
    assert not out["idle_slot_touched"] and out["state_dtype"] == "float32" and out["logit_std"] > 0.3


def _without_the_norms_of_b_and_c(real):
    def norm(x, scale, eps):
        return x.astype(jnp.float32) * scale if x.shape[-1] == 8 else real(x, scale, eps)  # d_state = 8: B and C

    return norm


# Tolerances: the chip's (benchmarks/drivers/serve_jamba.py and
# drivers/serve.py).  A bf16 program is inside them; fp8 weights and a left-out
# inner norm are refused through the pool, a state rounded to bf16 between
# calls by the check that isolates it (RULE_TOL).
@pytest.mark.parametrize("variant", ["as_published", "fp8_weights", "bf16_state", "no_inner_norm"])
def test_bf16_program_is_inside_the_chip_tolerances_and_each_departure_is_not(variant, monkeypatch):
    cfg = tiny(jnp.bfloat16)
    params = crafted(cfg)
    if variant == "no_inner_norm":
        monkeypatch.setattr(jamba, "_rms_norm", _without_the_norms_of_b_and_c(jamba._rms_norm))
    llm = ShardedLLM(cfg, tp=1, init=driver.fp8_weights(params) if variant == "fp8_weights" else params)
    if variant == "bf16_state":
        with driver.departure("bf16_state"):
            out = driver.compare(llm, PROMPT, page=PAGE, chunk=CHUNK)
    else:
        out = driver.compare(llm, PROMPT, page=PAGE, chunk=CHUNK, ref_params=params)
    assert out["ok"] == (variant == "as_published"), out
    if variant == "as_published":
        assert out["rule_alone_err"] < 2e-5 and out["k_rel_err"] < 0.02 and out["state_rel_err"] < 0.02 and out["window_rel_err"] < 0.01
    elif variant == "bf16_state":
        assert out["rule_alone_err"] > 5 * driver.RULE_TOL and out["rule_alone_max_err"] > driver.RULE_MAX_TOL
    elif variant == "fp8_weights":
        assert out["rule_alone_err"] < 2e-5 and max(out["k_rel_err"], out["state_rel_err"]) > 0.08  # the recurrence is sound; the weights are not


def test_the_departure_is_seen_by_the_check_that_isolates_it():
    """What a traced run of the cell does: the program as published is ok, and
    a bf16 state tried on the recurrence alone comes out not ok."""
    cfg = tiny(jnp.bfloat16)
    llm = ShardedLLM(cfg, tp=1, init=crafted(cfg))
    out = driver.compare(llm, PROMPT, page=PAGE, chunk=CHUNK, departures=("bf16_state",))
    assert out["ok"] and not out["bf16_state"]["ok"], out
    assert ssm.selective_scan.__module__ == "ray_tpu.ops.selective_scan"  # the departure stood in front of it only for its block


def test_published_sizes():
    whole = JambaConfig()
    assert whole.num_params() == 3_029_337_472 and whole.active_params_per_token() == whole.num_params()
    assert [i for i, k in enumerate(whole.layer_kinds) if k == "attn"] == [7, 21] and whole.layer_kinds.count("mamba") == 26
    assert (whole.d_inner, whole.head_dim, whole.padded_vocab) == (5120, 128, 65536)
    n = whole._layer_params()
    assert (n["mamba"], n["attn"], n["ffn"]) == (41_241_792, 13_762_560, 62_919_680)
    # the tree holds what the count says (the embedding padded to 128 rows, and no second matrix for the head)
    cfg = tiny(jnp.float32)
    shapes = jax.eval_shape(JambaModel(cfg).init, jax.random.PRNGKey(0))
    assert "out_head" not in shapes
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == cfg.num_params() + (cfg.padded_vocab - cfg.vocab_size) * cfg.dim
    pool = jax.eval_shape(lambda: JambaModel(whole).init_pages(128 * 128, 16, 128))
    assert [a.shape for a in pool] == [(2, 16384, 16, 1, 128)] * 2 + [(26, 128, 16, 40, 128), (26, 128, 3, 40, 128)]
    assert sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in pool[2:]) == 128 * 9_318_400  # 8.52 MB of state + 0.80 MB of window a slot
    with pytest.raises(ValueError, match="lanes"):
        JambaConfig(dim=100)
    with pytest.raises(ValueError, match="slots"):
        JambaModel(cfg).init_pages(8, PAGE)
    with pytest.raises(NotImplementedError):
        JambaModel(cfg).apply(None, None)


# ------------------------------------------------------------------ the engine


def _ref_greedy(cfg, params, prompt, n_new, buf=160):
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
    eng = InferenceEngine(llm, EngineConfig(num_slots=3, page_size=PAGE, max_seq_len=160, prefill_chunk=CHUNK, max_new_tokens=5, gauge_period_s=0.0), deployment="t")
    yield cfg, llm, eng
    eng.shutdown()


PROMPTS = [[5, 7, 9], list(PROMPT[:40]), list(PROMPT[:75])]  # one, two and three chunks


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
        assert eng.submit(list(map(int, p)), 5).sink.result(timeout=300) == w
    assert eng.stats()["state_resets"] == resets + 3 and eng.compile_stats() == {"prefill": 1, "decode": 1}
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    fresh = InferenceEngine(llm, EngineConfig(num_slots=1, page_size=PAGE, max_seq_len=160, prefill_chunk=CHUNK, max_new_tokens=5), deployment="f")
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
    # at rest: a compaction copies the members the model calls pages and hands the state on as it is
    assert eng._pool_roles == ("pages", "pages", "state", "state")
    before = [np.asarray(a) for a in eng._pages[2:]]
    eng.defrag()
    assert all(np.array_equal(a, np.asarray(b)) for a, b in zip(before, eng._pages[2:]))


# ------------------------------------------- the pool's contract is the model's


def _engine_stats(cfg, params=None, slots=2):
    import time

    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    llm = ShardedLLM(cfg, tp=1, init=params if params is not None else "random")
    eng = InferenceEngine(llm, EngineConfig(num_slots=slots, page_size=PAGE, max_seq_len=64, prefill_chunk=CHUNK if isinstance(cfg, JambaConfig) else 64, max_new_tokens=3, gauge_period_s=0.0), deployment="c")
    try:
        eng.submit([3, 4, 5, 6], 3).sink.result(timeout=300)
        eng._wake.set()
        time.sleep(0.3)  # an idle tick publishes
        return eng.stats(), eng._pool_roles
    finally:
        eng.shutdown()


def test_a_dense_model_with_per_slot_state_reports_state_bytes_and_no_moe_keys():
    """The engine reads what each member of the pool is from the model: a
    model with per-slot state and NO routing counter (this one) reports
    ``state_bytes`` and nothing of experts; a dense model with pages alone
    reports neither; OLMoE's and Qwen3-Next's keys are what they were."""
    from test_qwen3_next import tiny as qwen_tiny

    MOE = {"moe_assignments", "moe_assignments_held", "moe_assignments_seen", "moe_expert_load"}
    STATE = {"state_bytes", "state_resets"}
    cfg = tiny(jnp.float32)
    st, roles = _engine_stats(cfg, crafted(cfg))
    assert roles == ("pages", "pages", "state", "state") and STATE <= set(st) and not MOE & set(st)
    assert st["state_bytes"] == 2 * 3 * (8 * 128 * 4 + 3 * 128 * 4) and st["state_resets"] == 1  # slots x Mamba layers x (state + window), float32 here
    st, roles = _engine_stats(LlamaConfig.tiny(compute_dtype=jnp.float32))
    assert roles == ("pages", "pages") and not (MOE | STATE) & set(st)
    st, roles = _engine_stats(LlamaConfig.tiny(compute_dtype=jnp.float32, n_experts=4, n_experts_per_tok=2, qk_norm=True))
    assert roles == ("pages", "pages", "counter") and MOE <= set(st) and not STATE & set(st) and len(st["moe_expert_load"]) == 4
    st, roles = _engine_stats(qwen_tiny(jnp.float32))
    assert roles == ("pages", "pages", "counter", "state", "state", "expert_reads") and (MOE | STATE | {"moe_expert_reads"}) <= set(st) and len(st["moe_expert_load"]) == 4
    assert st["state_bytes"] == 2 * 3 * (4 * 16 * 16 * 4 + 3 * 128 * 4)
