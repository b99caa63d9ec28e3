"""Moonlight-16B-A3B's block on the normal path (models/deepseek_v3.py: a
latent cache row, the absorbed walk through llama.py's ``_paged_attend``;
parallel/moe.py's router told sigmoid, a selection bias, renormalise and
scale; serve/llm.py; the engine over a pool of ONE pages member) against the
plain float32 reference (benchmarks/reference/deepseek_v3_ref.py), at a tiny
size on the CPU.  The comparison is the one the benchmark's traced run makes
on the chip (benchmarks/drivers/serve_mla_moe.py compare)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.drivers import serve_mla_moe as driver  # noqa: E402
from benchmarks.reference import deepseek_v3_ref as ref_mod  # noqa: E402
from ray_tpu.models.deepseek_v3 import DeepseekV3Config, DeepseekV3Model  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.parallel import moe  # noqa: E402
from ray_tpu.serve.llm import ShardedLLM  # noqa: E402

PAGE, CHUNK = 8, 32
PROMPT = np.random.default_rng(5).integers(1, 250, CHUNK + CHUNK // 3 + 5).astype(np.int32)  # two chunks, the second ragged


def tiny(dtype, **kw):
    """The dense layer and two of experts at 64 wide: 8 experts, 3 a token, a latent row of 32 + 8."""
    base = dict(
        vocab_size=250, dim=64, n_layers=3, n_heads=4, hidden_dim=32, dense_hidden_dim=96, n_experts=8, n_experts_per_tok=3, n_shared_experts=2,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, max_seq_len=256, compute_dtype=dtype, param_dtype=dtype,
    )
    return DeepseekV3Config(**{**base, **kw})


def crafted(cfg):
    """``DeepseekV3Model.init``'s tree with the mixers' and experts' matrices
    x3 and the router x10 (at 64 wide and N(0, 0.02) every score is near zero,
    every softmax flat and the experts hardly move the residual stream), and
    with norm scales that are not their initial ones."""
    p = DeepseekV3Model(dataclasses.replace(cfg, param_dtype=jnp.float32)).init(jax.random.PRNGKey(0))
    k = iter(jax.random.split(jax.random.PRNGKey(7), 8))
    m, a, d = dict(p["moe"]), dict(p["attn"]), dict(p["dense"])
    for name in ("w_gate", "w_up", "w_down", "ws_gate", "ws_up", "ws_down"):
        m[name] = m[name] * 3.0
    m["router"] = m["router"] * 10.0
    for name in ("wq", "w_dkv", "w_uk", "w_uv", "wo"):
        a[name] = a[name] * 3.0
    for tree, names in ((m, ("ffn_norm",)), (a, ("attn_norm", "kv_norm")), (d, ("ffn_norm",))):
        for name in names:
            tree[name] = tree[name] + 0.2 * jax.random.normal(next(k), tree[name].shape)
    out = {**p, "moe": m, "attn": a, "dense": d, "final_norm": p["final_norm"] + 0.1}
    return jax.tree.map(lambda x: x.astype(cfg.param_dtype), out)


# --------------------------------------------------- the program and the reference


def test_float32_program_matches_the_reference_through_the_pool():
    """Prefill in two engine chunks (the second ragged) on a slot that was
    used before, then eight decode steps through the absorbed walk, against
    the reference's one full UNABSORBED forward: logits, every layer's latent
    rows, the choices, the counter, and each part alone -- all within 1e-4."""
    cfg = tiny(jnp.float32)
    llm = ShardedLLM(cfg, tp=1, init=crafted(cfg))
    tight = dict(row_tol=1e-4, row_max_tol=1e-4, logit_tol=1e-4, margin=1e-5, attn_tol=1e-4, ffn_tol=1e-4)
    out = driver.compare(llm, PROMPT, page=PAGE, chunk=CHUNK, **tight)
    assert out["ok"], out
    assert (out["chunks"], out["decode_steps"], out["dense_layers"], out["latent_dim"], out["row_dim"], out["pool_members"]) == (2, 8, 1, 40, 128, 2)
    assert not out["padding_written"]
    assert out["routing_agreement"] == 1.0 and out["router_weight_err"] < 1e-6 and not out["routing_copy_differs"]
    assert out["moe_load_total"] == (len(PROMPT) + 8 + CHUNK // 2) * 2 * 3 and out["moe_load_miscount"] == 0  # two expert layers, 3 a token
    assert out["bias_decides_share"] > 0.3  # the selection bias is not along for the ride


@pytest.fixture(scope="module")
def bf16_check():
    """The chip's comparison at the chip's tolerances, every departure tried: once for the cases below."""
    cfg = tiny(jnp.bfloat16)
    llm = ShardedLLM(cfg, tp=1, init=crafted(cfg))
    return driver.compare(llm, PROMPT, page=PAGE, chunk=CHUNK, departures=tuple(driver.DEPARTURES))


# Tolerances: the chip's (benchmarks/drivers/serve_mla_moe.py).  A bf16
# program is inside them; each planted departure is refused by the one check
# alone that sees it.
@pytest.mark.parametrize("variant", ["as_published", *driver.DEPARTURES])
def test_bf16_program_is_inside_the_chip_tolerances_and_each_departure_is_not(variant, bf16_check):
    out = bf16_check
    if variant == "as_published":
        assert out["ok"] and out["routing_flips_above_margin"] == 0 and out["router_weight_err"] < 1e-5 and out["norm_alone_err"] < 1e-5, out
        assert out["row_rel_err"] < 0.01 and out["attn_alone_err"] < 0.01 and out["ffn_alone_err"] < 0.01
        return
    seen = out[variant]
    assert not seen["ok"], (variant, seen)
    room = {"norm": ("norm_alone_err", 10 * driver.NORM_TOL), "router": None, "ffn": ("ffn_alone_err", 2 * driver.FFN_TOL), "attn": ("attn_alone_err", 2 * driver.ATTN_TOL)}[driver.DEPARTURES[variant]]
    if room:  # refused with room, not by a hair
        assert seen[room[0]] > room[1], (variant, seen)
    else:
        assert seen["router_weight_err"] > 10 * driver.ROUTER_TOL or seen["router_flips"] > 10, (variant, seen)


def test_fp8_weights_are_outside_the_chip_tolerances_by_several_limits():
    """The limits' second reading (the driver's table): the program on
    ``fp8_weights`` of its weights, the reference on the weights themselves,
    is refused -- by the rows, the mixer and the expert layer each, not by
    one limit's hair."""
    cfg = tiny(jnp.bfloat16)
    own = crafted(cfg)
    llm = ShardedLLM(cfg, tp=1, init=driver.fp8_weights(own))
    out = driver.compare(llm, PROMPT, page=PAGE, chunk=CHUNK, ref_params=own)
    assert not out["ok"], out
    assert out["row_rel_err"] > driver.ROW_REL_TOL and out["attn_alone_err"] > driver.ATTN_TOL and out["ffn_alone_err"] > driver.FFN_TOL, out
    assert out["norm_alone_err"] <= driver.NORM_TOL and out["router_weight_err"] <= driver.ROUTER_TOL  # float32 parts on the reference's inputs: untouched
    # a norm scale or a bias is not a matrix: left as it is
    assert all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(jax.tree.leaves(driver.fp8_weights(own)), jax.tree.leaves(own)) if a.ndim < 2)


def test_a_planted_departure_leaves_nothing_behind():
    from ray_tpu.models import deepseek_v3

    cfg = tiny(jnp.float32)
    llm = ShardedLLM(cfg, tp=1, init=crafted(cfg))
    before = (deepseek_v3._rms_norm, moe.route, moe.route_sigmoid, llm.model.config, llm.params)
    for which in driver.DEPARTURES:
        with driver.departure(which, llm):
            pass
        assert (deepseek_v3._rms_norm, moe.route, moe.route_sigmoid, llm.model.config, llm.params) == before and not vars(llm.model).keys() - {"config"}
    with pytest.raises(ValueError):
        with driver.departure("no_such_departure", llm):
            pass


def test_absorbed_equals_unabsorbed_on_one_layer_in_float32():
    """The mixer through a pool -- chunks, then one row a step, every head on
    one 40-wide row whose first 32 are the values -- against the reference's
    per-head keys and values expanded from the latent, [S, S] softmax."""
    cfg = tiny(jnp.float32)
    params = crafted(cfg)
    model = DeepseekV3Model(cfg)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(61, 64)) * 0.5, jnp.float32)
    ap = jax.tree.map(lambda a: a[1], params["attn"])
    got, rows = driver.mixer_alone(model, ap, x, page=PAGE, chunk=CHUNK, prefill_len=50)
    lp = ref_mod.to_published_layout(params, cfg.first_k_dense)["layers"][1]
    kw = {k: v for k, v in driver.reference_kwargs(cfg).items() if k in ("n_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta", "eps")}
    with jax.default_matmul_precision("highest"):
        want, want_rows = ref_mod.latent_attention(ref_mod._norm(x, lp["attn_norm"], cfg.norm_eps), lp, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    np.testing.assert_allclose(rows, np.asarray(want_rows), atol=2e-6)
    assert rows.shape == (61, cfg.kv_lora_rank + cfg.qk_rope_head_dim)  # the values of a row; the pool pads it to cache_row_dim


# ------------------------------------------------------------ the routed layer


def _layer_inputs(seed=4, T=24, E=32, H=16, X=64, scale=0.3):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return f(T, E), f(E, X) * 0.5, f(X) * 0.2, f(X, E, H) * scale, f(X, E, H) * scale, f(X, H, E) * scale


def test_a_nonzero_bias_changes_the_chosen_set_and_never_a_weight():
    h, router, bias, *_ = _layer_inputs()
    w0, c0 = moe.route_sigmoid(h, router, 6)
    w1, c1 = moe.route_sigmoid(h, router, 6, bias)
    sigma = jax.nn.sigmoid(jnp.dot(h, router, precision=lax.Precision.HIGHEST))
    changed = (np.sort(np.asarray(c0), -1) != np.sort(np.asarray(c1), -1)).any(-1)
    assert changed.mean() > 0.5  # the bias decides
    assert np.array_equal(np.asarray(w1), np.asarray(jnp.take_along_axis(sigma, c1, -1)))  # the weights are the scores' own, bit for bit
    assert np.array_equal(np.asarray(c1), np.asarray(lax.top_k(sigma + bias, 6)[1]))
    zw, zc = moe.route_sigmoid(h, router, 6, jnp.zeros_like(bias))
    assert np.array_equal(np.asarray(zc), np.asarray(c0)) and np.array_equal(np.asarray(zw), np.asarray(w0))
    _, _, _, wg, wu, wd = _layer_inputs()
    for told in (dict(scoring="tanh"), dict(scoring="softmax", bias=bias)):  # no such router here
        with pytest.raises(ValueError):
            moe.dropless_moe_ffn(h, router, wg, wu, wd, top_k=6, **told)


@pytest.mark.parametrize("renormalize", [True, False])
def test_the_four_quarter_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once(renormalize):
    """Offsets 0, 16, 32, 48 of 64 experts under the sigmoid router with its
    bias and scale: the four holders' parts add up to the reference's routed
    sum over all 64; the shared expert is the model's to add, once."""
    h, router, bias, wg, wu, wd = _layer_inputs()
    told = dict(top_k=6, renormalize=renormalize, scoring="sigmoid", bias=bias, scale=2.446)
    sigma, _, chosen = ref_mod.route(h, router, bias, 6)
    with jax.default_matmul_precision("highest"):
        want = ref_mod.expert_ffn(h, ref_mod.routed_weights(sigma, chosen, norm_topk_prob=renormalize, routed_scaling_factor=2.446), chosen, wg, wu, wd)
    parts = []
    for off in (0, 16, 32, 48):
        held = slice(off, off + 16)
        y, got_chosen = moe.dropless_moe_ffn(h, router, wg[held], wu[held], wd[held], expert_offset=off, **told)
        assert np.array_equal(np.asarray(got_chosen), np.asarray(chosen))  # every holder routes over all 64
        parts.append(y)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want), atol=3e-4)
    whole, _ = moe.dropless_moe_ffn(h, router, wg, wu, wd, **told)  # all held: Moonlight's call
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=3e-4)
    # the model's layer = the routed sum + the shared expert once, unweighted
    cfg = tiny(jnp.float32, dim=32, hidden_dim=16, n_experts=64, n_experts_per_tok=6, norm_topk_prob=renormalize)
    rng = np.random.default_rng(9)
    ws = [jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32) for s in ((32, 32), (32, 32), (32, 32))]
    mp = {"ffn_norm": jnp.ones(32), "router": router, "router_bias": bias, "w_gate": wg, "w_up": wu, "w_down": wd, "ws_gate": ws[0], "ws_up": ws[1], "ws_down": ws[2]}
    x = h[None] * 0.7
    out, _ = DeepseekV3Model(cfg)._ffn(x, mp)
    with jax.default_matmul_precision("highest"):
        g = ref_mod._norm(x[0], mp["ffn_norm"], cfg.norm_eps)
        s2, _, c2 = ref_mod.route(g, router, bias, 6)
        layer = ref_mod.expert_ffn(g, ref_mod.routed_weights(s2, c2, norm_topk_prob=renormalize, routed_scaling_factor=2.446), c2, wg, wu, wd) + ref_mod.swiglu(g, *ws)
    np.testing.assert_allclose(np.asarray(out[0] - x[0]), np.asarray(layer), atol=3e-4)


def _parent_route(h, router_w, top_k):
    """``parallel/moe.route`` as it stood before the sigmoid router came to stand beside it."""
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32), precision=lax.Precision.HIGHEST)
    return lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)


@pytest.mark.parametrize("name, experts, top_k, renormalize", [("olmoe", 64, 8, False), ("qwen3_next", 512, 10, True)])
def test_the_softmax_routers_results_are_bit_equal_to_the_parents(name, experts, top_k, renormalize):
    rng = np.random.default_rng(11)
    h = jnp.asarray(rng.normal(size=(48, 64)), jnp.bfloat16)
    router = jnp.asarray(rng.normal(size=(64, experts)) * 0.3, jnp.bfloat16)
    for got, want in zip(jax.jit(lambda h, w: moe.route(h, w, top_k))(h, router), jax.jit(lambda h, w: _parent_route(h, w, top_k))(h, router)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    # the layer at its own settings is the layer told softmax, no bias, scale 1
    held = 16
    wg, wu, wd = (jnp.asarray(rng.normal(size=s) * 0.3, jnp.bfloat16) for s in ((held, 64, 32), (held, 64, 32), (held, 32, 64)))
    a = moe.dropless_moe_ffn(h, router, wg, wu, wd, top_k=top_k, renormalize=renormalize, expert_offset=16)
    b = moe.dropless_moe_ffn(h, router, wg, wu, wd, top_k=top_k, renormalize=renormalize, expert_offset=16, scoring="softmax", bias=None, scale=1.0)
    assert all(np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(a, b))


def test_published_sizes():
    cut = DeepseekV3Config(n_layers=8)
    assert cut.num_params() == 4_847_999_424 and (cut.latent_dim, cut.cache_row_dim, cut.qk_head_dim) == (576, 640, 192)
    whole = DeepseekV3Config()
    assert abs(whole.num_params() - 15_960e6) < 5e6  # "16B"
    assert 2.8e9 < whole.active_params_per_token() < 3.0e9  # "A3B"
    n = cut._layer_params()
    assert (n["attn"] - 2 * 2048, n["dense"], n["expert"]) == (13_763_072, 3 * 2048 * 11264, 8_650_752)
    assert n["attn"] + n["outside_experts"] == 31_199_808 and n["attn"] + n["dense"] == 82_973_184
    # the tree holds what the count says (embedding and head padded to 128 rows)
    cfg = tiny(jnp.float32)
    leaves = jax.tree.leaves(jax.eval_shape(DeepseekV3Model(cfg).init, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in leaves) == cfg.num_params() + 2 * (cfg.padded_vocab - cfg.vocab_size) * cfg.dim
    with pytest.raises(ValueError):
        DeepseekV3Config(n_kv_heads=16)  # the cache is one row a position, whatever the published file's key says


# ------------------------------------------------------------------ the engine


def _ref_greedy(cfg, params, prompt, n_new, buf=192):
    """Greedy tokens from the reference's full forward, no cache: the
    sequence lives in a buffer of one length (a causal forward's logits do
    not see what follows a position)."""
    pub = ref_mod.to_published_layout(params, cfg.first_k_dense)
    seq = np.zeros(buf, np.int32)
    seq[: len(prompt)] = prompt
    for i in range(len(prompt), len(prompt) + n_new):
        logits = ref_mod.forward(pub, jnp.asarray(seq), **driver.reference_kwargs(cfg)).logits
        seq[i] = int(np.argmax(np.asarray(logits[i - 1])[: cfg.vocab_size]))
    return seq[len(prompt) : len(prompt) + n_new].tolist()


@pytest.fixture(scope="module")
def served():
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = tiny(jnp.float32)
    llm = ShardedLLM(cfg, tp=1, init=crafted(cfg))
    eng = InferenceEngine(llm, EngineConfig(num_slots=3, page_size=PAGE, max_seq_len=192, prefill_chunk=CHUNK, max_new_tokens=5, gauge_period_s=0.0), deployment="t")
    yield cfg, llm, eng
    eng.shutdown()


PROMPTS = [[5, 7, 9], list(PROMPT[:40]), list(PROMPT) + list(PROMPT[:30])]  # one, two and three chunks


def test_the_engine_serves_a_fleet_of_mixed_lengths_on_one_shape_each(served):
    cfg, llm, eng = served
    outs = [r.sink.result(timeout=300) for r in [eng.submit(list(map(int, p)), 5) for p in PROMPTS]]
    for p, o in zip(PROMPTS, outs):
        assert o == _ref_greedy(cfg, llm.params, p, 5)  # the reference: no cache, no chunks, no pool, unabsorbed
    assert eng.compile_stats() == {"prefill": 1, "decode": 1}


def test_a_row_is_the_same_alone_and_among_others_and_a_reused_slot_starts_clean(served):
    """Each prompt again, alone, on slot 0 -- whose pages the request before
    it left full of its own rows: the same tokens as in the fleet, and as a
    fresh engine's."""
    cfg, llm, eng = served
    want = [_ref_greedy(cfg, llm.params, p, 5) for p in PROMPTS]
    for p, w in zip(PROMPTS[::-1], want[::-1]):
        assert eng.submit(list(map(int, p)), 5).sink.result(timeout=300) == w
    assert eng.compile_stats() == {"prefill": 1, "decode": 1}
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    fresh = InferenceEngine(llm, EngineConfig(num_slots=1, page_size=PAGE, max_seq_len=192, prefill_chunk=CHUNK, max_new_tokens=5), deployment="f")
    try:
        assert fresh.submit(list(map(int, PROMPTS[1])), 5).sink.result(timeout=300) == want[1]
    finally:
        fresh.shutdown()


def test_defrag_moves_latent_pages(served):
    cfg, llm, eng = served
    want = _ref_greedy(cfg, llm.params, PROMPTS[2], 5)
    first = eng.submit(list(map(int, PROMPTS[0])), 5)  # takes the lowest pages, then frees them
    second = eng.submit(list(map(int, PROMPTS[2])), 5)
    first.sink.result(timeout=300)
    moved = eng.defrag()
    assert second.sink.result(timeout=300) == want and "moves" in moved
    # at rest: a compaction copies the one pages member and hands the counter on as it is
    counter = np.asarray(eng._pages[1])
    eng.defrag()
    assert np.array_equal(counter, np.asarray(eng._pages[1])) and eng.compile_stats() == {"prefill": 1, "decode": 1}


def test_the_pool_is_one_latent_row_a_position_and_the_engine_says_so(served):
    import time

    cfg, llm, eng = served
    roles, pool = llm.model.pool_roles(), eng._pages
    assert roles == ("pages", "counter") and len(pool) == 2
    # one row a position: kv_lora_rank + qk_rope_head_dim values, padded to whole lane tiles; no expanded key or value anywhere
    assert cfg.latent_dim == cfg.kv_lora_rank + cfg.qk_rope_head_dim == 40 and cfg.cache_row_dim == 128
    assert pool[0].shape == (cfg.n_layers, eng.cfg.pool_pages(), PAGE, cfg.cache_row_dim)
    assert not np.asarray(pool[0])[..., cfg.latent_dim :].any()  # the padding stays zero
    steps, live = eng.stats()["decode_steps"], eng.stats()["ctx_positions_live"]
    eng.submit([3, 4, 5, 6], 3).sink.result(timeout=300)
    eng._wake.set()
    time.sleep(0.3)  # an idle tick publishes
    st = eng.stats()
    assert st["cache_bytes_per_position"] == cfg.n_layers * 128 * 4  # float32 here: layers x (32 + 8 values, padded to 128) x 4 B
    # alone, the request decodes at positions 4 and 5 (the first token comes from the chunk): 5 + 6 positions held
    assert st["decode_steps"] - steps == 2 and st["ctx_positions_live"] - live == 11
    assert len(st["moe_expert_load"]) == cfg.n_experts and st["moe_assignments_seen"] % ((cfg.n_layers - 1) * cfg.n_experts_per_tok) == 0


def test_every_model_reports_what_its_cache_keeps_a_position():
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = LlamaConfig.tiny(compute_dtype=jnp.bfloat16)  # 2 layers, 2 KV heads of 16
    eng = InferenceEngine(ShardedLLM(cfg, tp=1), EngineConfig(num_slots=2, page_size=8, max_seq_len=64, prefill_chunk=16), deployment="d")
    try:
        assert eng.stats()["cache_bytes_per_position"] == 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 2
    finally:
        eng.shutdown()
    # at OLMoE's published widths: K and V, 8 layers, 16 heads of 128, bf16
    assert 2 * 8 * 16 * 128 * 2 == 65_536 and 8 * DeepseekV3Config().cache_row_dim * 2 == 10_240 and 8 * 576 * 2 == 9_216
