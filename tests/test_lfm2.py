"""LFM2-24B-A2B's block on the normal path (models/lfm2.py: gated
short-convolution mixers whose windows ride in the pool by slot, two
grouped-query layers with a per-head QK-norm that keep pages, leading dense
layers, then parallel/moe.py's router told sigmoid, a selection bias and the
published epsilon; serve/llm.py; the engine over a pool of pages, a counter
and ONE state member) against the plain float32 reference
(benchmarks/reference/lfm2_ref.py), at a tiny size on the CPU.  The
comparison is the one the benchmark's traced run makes on the chip
(benchmarks/drivers/serve_conv_moe.py compare)."""

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

from benchmarks.drivers import serve_conv_moe as driver  # noqa: E402
from benchmarks.reference import lfm2_ref as ref_mod  # noqa: E402
from ray_tpu.models import jamba as jamba_mod  # noqa: E402
from ray_tpu.models.jamba import JambaConfig, JambaModel  # noqa: E402
from ray_tpu.models.lfm2 import RENORM_EPS, Lfm2MoeConfig, Lfm2MoeModel  # noqa: E402
from ray_tpu.parallel import moe  # noqa: E402
from ray_tpu.serve.llm import ShardedLLM  # noqa: E402

PAGE, CHUNK = 8, 32
PROMPT = np.random.default_rng(5).integers(1, 250, CHUNK + CHUNK // 3 + 5).astype(np.int32)  # two chunks, the second ragged: 32 does not divide 47
KINDS = ("conv", "conv", "full_attention", "conv", "full_attention", "conv")


def tiny(dtype, **kw):
    """Six layers at 64 wide with both mixers and both FFNs: two dense conv
    layers, then an attending and a conv layer with experts, twice; 8 experts,
    2 a token, 4 query heads on 2 KV heads of 16."""
    base = dict(
        vocab_size=250, dim=64, n_layers=6, layer_types=KINDS, n_heads=4, n_kv_heads=2, hidden_dim=32, dense_hidden_dim=96, n_dense_layers=2,
        n_experts=8, n_experts_per_tok=2, max_seq_len=256, compute_dtype=dtype, param_dtype=dtype,
    )
    return Lfm2MoeConfig(**{**base, **kw})


def crafted(cfg):
    """``Lfm2MoeModel.init``'s tree with the mixers' and FFNs' matrices x3, the
    taps x20 and the router x10 (at 64 wide and N(0, 0.02) every score is near
    zero, the taps hardly move the residual stream and the experts neither),
    and with norm scales that are not their initial ones (``driver.with_drawn_norm_scales``:
    at 1 a head norm after rotary is the head norm before it)."""
    p = Lfm2MoeModel(dataclasses.replace(cfg, param_dtype=jnp.float32)).init(jax.random.PRNGKey(0))
    gain = {"conv": {"w_in": 3.0, "conv_w": 20.0, "w_out": 3.0}, "attn": {n: 3.0 for n in ("wq", "wk", "wv", "wo")},
            "dense": {n: 3.0 for n in ("w_gate", "w_up", "w_down")}, "moe": {"router": 10.0, "w_gate": 3.0, "w_up": 3.0, "w_down": 3.0}}
    out = {k: {n: a * gain[k].get(n, 1.0) for n, a in v.items()} if isinstance(v, dict) else v for k, v in p.items()}
    out = driver.with_drawn_norm_scales(out, 7)
    return jax.tree.map(lambda x: x.astype(cfg.param_dtype), out)


# --------------------------------------------------- the program and the reference


def test_float32_program_matches_the_reference_through_the_pool():
    """Prefill in two engine chunks (the chunk does not divide the prompt: the
    second is ragged) on a slot that was used before, then eight decode steps,
    against the reference's one full forward with a padded convolution and no
    window: logits, the attending layers' K/V, every conv layer's window, the
    choices, the counter, and each part alone -- all within 1e-4."""
    cfg = tiny(jnp.float32)
    llm = ShardedLLM(cfg, tp=1, init=crafted(cfg))
    tight = dict(kv_tol=1e-4, kv_max_tol=1e-4, window_tol=1e-4, logit_tol=1e-4, margin=1e-5, conv_tol=1e-4, conv_max_tol=1e-4, conv_window_tol=1e-4, attn_tol=1e-4, ffn_tol=1e-4)
    out = driver.compare(llm, PROMPT, page=PAGE, chunk=CHUNK, **tight)
    assert out["ok"], out
    assert len(PROMPT) % CHUNK and (out["chunks"], out["decode_steps"], out["dense_layers"], out["layer_kinds"], out["kv_layers"]) == (2, 8, 2, "ccacac", 2)
    assert out["pool_roles"] == ["pages", "pages", "counter", "state"] and out["window_shape"] == [4, 2, 2, 64] and not out["idle_slot_touched"]
    assert out["routing_agreement"] == 1.0 and out["router_weight_err"] < 1e-6 and not out["routing_copy_differs"]
    assert out["moe_load_total"] == (len(PROMPT) + 8 + CHUNK // 2) * 4 * 2 and out["moe_load_miscount"] == 0  # four expert layers, 2 a token
    assert out["bias_decides_share"] > 0.3  # the selection bias is not along for the ride


@pytest.fixture(scope="module")
def bf16_check():
    """The chip's comparison at the chip's tolerances, every departure tried: once for the cases below."""
    cfg = tiny(jnp.bfloat16)
    llm = ShardedLLM(cfg, tp=1, init=crafted(cfg))
    return driver.compare(llm, PROMPT, page=PAGE, chunk=CHUNK, departures=tuple(driver.DEPARTURES))


# Tolerances: the chip's (benchmarks/drivers/serve_conv_moe.py).  A bf16
# program is inside them; each planted departure is refused by the one check
# alone that sees it.
@pytest.mark.parametrize("variant", ["as_published", *driver.DEPARTURES])
def test_bf16_program_is_inside_the_chip_tolerances_and_each_departure_is_not(variant, bf16_check):
    out = bf16_check
    if variant == "as_published":
        assert out["ok"] and out["routing_flips_above_margin"] == 0 and out["router_weight_err"] < 1e-5 and out["norm_alone_err"] < 1e-5, out
        assert max(out["k_rel_err"], out["v_rel_err"], out["window_rel_err"], out["conv_alone_err"], out["attn_alone_err"], out["ffn_alone_err"], out["dense_alone_err"]) < 0.015
        return
    seen = out[variant]
    assert not seen["ok"], (variant, seen)
    room = {"norm": ("norm_alone_err", 10 * driver.NORM_TOL), "router": None, "ffn": ("ffn_alone_err", 2 * driver.FFN_TOL), "dense": ("dense_alone_err", 2 * driver.FFN_TOL),
            "conv": ("conv_alone_max_err", 2 * driver.CONV_MAX_TOL), "attn": ("attn_alone_err", 2 * driver.ATTN_TOL)}[driver.DEPARTURES[variant]]
    if room:  # refused with room, not by a hair
        assert seen[room[0]] > room[1], (variant, seen)
    else:
        assert seen["router_weight_err"] > 10 * driver.ROUTER_TOL or seen["router_flips"] > 10, (variant, seen)


def test_fp8_weights_are_outside_the_chip_tolerances_by_several_limits():
    """The limits' second reading (the driver's table): the program on
    ``fp8_weights`` of its weights, the reference on the weights themselves,
    is refused -- by the K/V, the windows and each mixer and FFN alone, not by
    one limit's hair."""
    cfg = tiny(jnp.bfloat16)
    own = crafted(cfg)
    llm = ShardedLLM(cfg, tp=1, init=driver.fp8_weights(own))
    out = driver.compare(llm, PROMPT, page=PAGE, chunk=CHUNK, ref_params=own)
    assert not out["ok"], out
    assert max(out["k_rel_err"], out["v_rel_err"]) > driver.KV_REL_TOL and out["window_rel_err"] > driver.WINDOW_REL_TOL, out
    assert out["conv_alone_err"] > driver.CONV_TOL and out["attn_alone_err"] > driver.ATTN_TOL and out["ffn_alone_err"] > driver.FFN_TOL and out["dense_alone_err"] > driver.FFN_TOL, out
    assert out["norm_alone_err"] <= driver.NORM_TOL and out["router_weight_err"] <= driver.ROUTER_TOL  # float32 parts on the reference's inputs: untouched


def test_a_planted_departure_leaves_nothing_behind():
    from ray_tpu.models import lfm2

    cfg = tiny(jnp.float32)
    llm = ShardedLLM(cfg, tp=1, init=crafted(cfg))
    state = lambda: (lfm2._rms_norm, lfm2.conv_window_taps, lfm2.conv_window_after, moe.route_sigmoid, llm.model.config, llm.params)  # noqa: E731
    before = state()
    for which in driver.DEPARTURES:
        with driver.departure(which, llm):
            pass
        assert state() == before and not vars(llm.model).keys() - {"config"}
    with pytest.raises(ValueError):
        with driver.departure("no_such_departure", llm):
            pass


# ------------------------------------------------------------ the window, shared with Jamba


def _old_mamba_window(conv, mi, slot, u, conv_w, conv_b, q_pos, q_valid):
    """``JambaModel._mamba``'s window code as it stood before the two
    functions were taken out of it (the parent's lines, verbatim)."""
    from ray_tpu.ops import selective_scan as ssm

    f32 = jnp.float32
    S, K = u.shape[1], conv_w.shape[0]
    tiles = ssm.channel_tiles
    win = conv[mi] if slot is None else lax.dynamic_slice_in_dim(conv[mi], slot, 1, axis=0)  # [B, k-1, R, 128]
    fresh = q_valid[:, 0] & (q_pos[:, 0] == 0)
    win = jnp.where(fresh[:, None, None, None], jnp.zeros_like(win), win)
    seq = jnp.concatenate([win, tiles(u)], axis=1)  # [B, k-1 + S, R, 128]
    w = tiles(conv_w.astype(f32))
    u = jax.nn.silu(sum(seq[:, j : j + S].astype(f32) * w[j] for j in range(K)) + tiles(conv_b.astype(f32)))
    if S == 1:
        win = jnp.where(q_valid[:, :, None, None], seq[:, 1:], seq[:, :-1])
    else:
        win = jax.vmap(lambda s, n: lax.dynamic_slice_in_dim(s, n, K - 1, axis=0))(seq, q_valid.sum(-1))
    conv = conv.at[mi].set(win) if slot is None else lax.dynamic_update_slice(conv, win[None], (mi, slot, 0, 0, 0))
    return u, conv, fresh


@pytest.mark.parametrize("call", ["decode", "chunk"])
def test_jambas_window_through_the_shared_functions_is_its_old_one(call):
    """Bit for bit, and operation for operation: the jaxpr of the new lines is the jaxpr of the old."""
    from ray_tpu.ops import selective_scan as ssm

    rng = np.random.default_rng(3)
    slots, K, Dn = 3, 4, 256
    conv = jnp.asarray(rng.normal(size=(2, slots, K - 1, Dn // 128, 128)), jnp.bfloat16)
    conv_w, conv_b = jnp.asarray(rng.normal(size=(K, Dn)), jnp.float32), jnp.asarray(rng.normal(size=(Dn,)), jnp.float32)
    if call == "decode":
        u = jnp.asarray(rng.normal(size=(slots, 1, Dn)), jnp.bfloat16)
        q_pos, q_valid, slot = jnp.asarray([[0], [5], [7]], jnp.int32), jnp.asarray([[True], [True], [False]]), None
    else:
        u = jnp.asarray(rng.normal(size=(1, 8, Dn)), jnp.bfloat16)
        q_pos, q_valid, slot = jnp.arange(16, 24, dtype=jnp.int32)[None], (jnp.arange(8) < 5)[None], 2

    def new(conv, u, conv_w, conv_b, q_pos, q_valid):
        y, seq, fresh = jamba_mod.conv_window_taps(conv, 1, slot, u, conv_w, q_pos, q_valid, ssm.channel_tiles)
        act = jax.nn.silu(y + ssm.channel_tiles(conv_b.astype(jnp.float32)))
        return act, jamba_mod.conv_window_after(conv, 1, slot, seq, q_valid), fresh

    old = lambda conv, u, conv_w, conv_b, q_pos, q_valid: _old_mamba_window(conv, 1, slot, u, conv_w, conv_b, q_pos, q_valid)  # noqa: E731
    args = (conv, u, conv_w, conv_b, q_pos, q_valid)
    for got, want in zip(jax.jit(new)(*args), jax.jit(old)(*args)):
        assert np.array_equal(np.asarray(got.astype(jnp.float32)), np.asarray(want.astype(jnp.float32)))
    assert str(jax.make_jaxpr(new)(*args)) == str(jax.make_jaxpr(old)(*args))
    # and the model's own mixer calls them: nothing of the window is left in _mamba
    import inspect

    src = inspect.getsource(JambaModel._mamba)
    assert "conv_window_taps(" in src and "conv_window_after(" in src and "dynamic_slice_in_dim" not in src
    assert JambaConfig().d_conv == 4


# ------------------------------------------------------------ the routed layer


def _layer_inputs(seed=4, T=24, E=32, H=16, X=64, scale=0.3):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return f(T, E), f(E, X) * 0.5, f(X) * 0.2, f(X, E, H) * scale, f(X, E, H) * scale, f(X, H, E) * scale


def test_a_nonzero_bias_changes_the_chosen_set_and_never_a_weight():
    h, router, bias, wg, wu, wd = _layer_inputs()
    told = dict(top_k=4, renormalize=True, scoring="sigmoid", scale=1.0, renorm_eps=RENORM_EPS)
    _, c0 = moe.dropless_moe_ffn(h, router, wg, wu, wd, **told)
    y1, c1 = moe.dropless_moe_ffn(h, router, wg, wu, wd, bias=bias, **told)
    changed = (np.sort(np.asarray(c0), -1) != np.sort(np.asarray(c1), -1)).any(-1)
    assert changed.mean() > 0.5  # the bias decides
    sigma = jax.nn.sigmoid(jnp.dot(h, router, precision=lax.Precision.HIGHEST))
    assert np.array_equal(np.asarray(c1), np.asarray(lax.top_k(sigma + bias, 4)[1]))
    # the weights are the scores' own over (their sum + 1e-6), the bias nowhere: the layer is the reference's loop over the chosen four
    with jax.default_matmul_precision("highest"):
        want = ref_mod.expert_ffn(h, ref_mod.routed_weights(sigma, c1, norm_topk_prob=True, routed_scaling_factor=1.0), c1, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(want), atol=3e-4)
    # a larger bias chooses otherwise again and still weighs nothing: rows that keep their set keep their result
    y2, c2 = moe.dropless_moe_ffn(h, router, wg, wu, wd, bias=bias * 2.0, **told)
    same = (np.sort(np.asarray(c2), -1) == np.sort(np.asarray(c1), -1)).all(-1)
    assert 0 < same.sum() < len(same)
    np.testing.assert_allclose(np.asarray(y2)[same], np.asarray(y1)[same], atol=1e-5)


def test_the_four_quarter_shares_add_up_to_the_uncut_layer():
    """Offsets 0, 16, 32, 48 of 64 experts under the sigmoid router with its
    bias and the published epsilon: the four holders' parts add up to the
    reference's routed sum over all 64 (model-configs guide section 4: what
    ties a share to the model)."""
    h, router, bias, wg, wu, wd = _layer_inputs()
    told = dict(top_k=4, renormalize=True, scoring="sigmoid", bias=bias, scale=1.0, renorm_eps=RENORM_EPS)
    sigma, _, chosen = ref_mod.route(h, router, bias, 4)
    with jax.default_matmul_precision("highest"):
        want = ref_mod.expert_ffn(h, ref_mod.routed_weights(sigma, chosen, norm_topk_prob=True, routed_scaling_factor=1.0), chosen, wg, wu, wd)
    parts = []
    for off in (0, 16, 32, 48):
        held = slice(off, off + 16)
        y, got_chosen = moe.dropless_moe_ffn(h, router, wg[held], wu[held], wd[held], expert_offset=off, **told)
        assert np.array_equal(np.asarray(got_chosen), np.asarray(chosen))  # every holder routes over all 64
        parts.append(y)
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want), atol=3e-4)
    whole, _ = moe.dropless_moe_ffn(h, router, wg, wu, wd, **told)  # all held: this model's call
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=3e-4)
    # the model's layer is that sum and nothing else: no shared expert
    cfg = tiny(jnp.float32, dim=32, hidden_dim=16, n_experts=64, n_experts_per_tok=4, n_heads=2, n_kv_heads=1)
    mp = {"ffn_norm": jnp.ones(32), "router": router, "router_bias": bias, "w_gate": wg, "w_up": wu, "w_down": wd}
    x = h[None] * 0.7
    out, _ = Lfm2MoeModel(cfg)._ffn(x, mp)
    with jax.default_matmul_precision("highest"):
        g = ref_mod._norm(x[0], mp["ffn_norm"], cfg.norm_eps)
        s2, _, c2 = ref_mod.route(g, router, bias, 4)
        layer = ref_mod.expert_ffn(g, ref_mod.routed_weights(s2, c2, norm_topk_prob=True, routed_scaling_factor=1.0), c2, wg, wu, wd)
    np.testing.assert_allclose(np.asarray(out[0] - x[0]), np.asarray(layer), atol=3e-4)


def _parent_dropless(h, router_w, w_gate, w_up, w_down, *, top_k, renormalize, expert_offset=0, scoring="softmax", bias=None, scale=1.0):
    """``parallel/moe.dropless_moe_ffn`` as it stood before it took the epsilon as an argument (the parent's lines)."""
    cd = h.dtype
    n_experts, held = router_w.shape[-1], w_gate.shape[0]
    weights, chosen = moe.route_sigmoid(h, router_w, top_k, bias) if scoring == "sigmoid" else moe.route(h, router_w, top_k)
    if renormalize:
        total = weights.sum(-1, keepdims=True)
        weights = weights / (total + 1e-20 if scoring == "sigmoid" else total)
    if scale != 1.0:
        weights = weights * scale
    dense_w = (jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32) * weights[..., None]).sum(-2)
    if held != n_experts:
        dense_w = dense_w[:, expert_offset : expert_offset + held]
    gate = jnp.einsum("te,xeh->xth", h, w_gate.astype(cd))
    up = jnp.einsum("te,xeh->xth", h, w_up.astype(cd))
    act = (jax.nn.silu(gate) * up).astype(jnp.float32) * dense_w.T[:, :, None]
    y = jnp.einsum("xth,xhe->te", act.astype(cd), w_down.astype(cd), preferred_element_type=jnp.float32)
    return y.astype(cd), chosen


@pytest.mark.parametrize("name, experts, told", [
    ("moonlight", 64, dict(top_k=6, renormalize=True, scoring="sigmoid", scale=2.446)),
    ("qwen3_next", 512, dict(top_k=10, renormalize=True, expert_offset=16)),
    ("olmoe", 64, dict(top_k=8, renormalize=False)),
])
def test_the_other_models_routed_layer_is_bit_equal_to_the_parents_at_their_own_settings(name, experts, told):
    rng = np.random.default_rng(11)
    h = jnp.asarray(rng.normal(size=(48, 64)), jnp.bfloat16)
    router = jnp.asarray(rng.normal(size=(64, experts)) * 0.3, jnp.bfloat16)
    held = 16 if "expert_offset" in told else experts
    wg, wu, wd = (jnp.asarray(rng.normal(size=s) * 0.3, jnp.bfloat16) for s in ((held, 64, 32), (held, 64, 32), (held, 32, 64)))
    if told.get("scoring") == "sigmoid":
        told = {**told, "bias": jnp.asarray(rng.normal(size=(experts,)) * 0.2, jnp.float32)}
    got = jax.jit(lambda *a: moe.dropless_moe_ffn(*a, **told))(h, router, wg, wu, wd)
    want = jax.jit(lambda *a: _parent_dropless(*a, **told))(h, router, wg, wu, wd)
    assert all(np.array_equal(np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32))) for a, b in zip(got, want))
    # and operation for operation: their programs are the parent's
    strip = lambda f: str(jax.make_jaxpr(f)(h, router, wg, wu, wd))  # noqa: E731
    assert strip(lambda *a: moe.dropless_moe_ffn(*a, **told)) == strip(lambda *a: _parent_dropless(*a, **told))
    # the epsilon is an argument: told 1e-6 a renormalised row's weights are another function, told the default's value the same
    if told["renormalize"] and told.get("scoring") == "sigmoid":
        eps = jax.jit(lambda *a: moe.dropless_moe_ffn(*a, **told, renorm_eps=1e-20))(h, router, wg, wu, wd)
        assert np.array_equal(np.asarray(eps[0].astype(jnp.float32)), np.asarray(got[0].astype(jnp.float32)))


def test_published_sizes():
    cut = Lfm2MoeConfig(n_layers=10)
    assert cut.num_params() == 5_267_090_176 and cut.layer_kinds == ("conv", "conv", "attn", "conv", "conv", "conv", "attn", "conv", "conv", "conv")
    whole = Lfm2MoeConfig()
    assert whole.num_params() == 23_843_661_440 and whole.active_params_per_token() == 2_326_881_920  # "24B", "A2B"
    assert whole.layer_kinds.count("attn") == 10 and whole.layer_kinds.count("conv") == 30 and whole.head_dim == 64
    assert Lfm2MoeConfig(n_layers=9).num_params() == 4_646_191_808  # the issue's fallback, not taken
    n = cut._layer_params()
    assert (n["conv"], n["attn"], n["expert"]) == (16_783_360, 10_485_888, 9_437_184)
    assert n["conv"] + n["outside_experts"] + 64 * n["expert"] == 620_898_368 and n["attn"] + n["outside_experts"] + 64 * n["expert"] == 614_600_896
    assert n["conv"] + n["dense"] == 89_139_200
    # the tree holds what the count says (the embedding padded to 128 rows)
    cfg = tiny(jnp.float32)
    leaves = jax.tree.leaves(jax.eval_shape(Lfm2MoeModel(cfg).init, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in leaves) == cfg.num_params() + (cfg.padded_vocab - cfg.vocab_size) * cfg.dim
    with pytest.raises(ValueError):
        Lfm2MoeConfig(n_layers=41)  # layer_types names forty
    with pytest.raises(ValueError):
        Lfm2MoeConfig(layer_types=("conv", "mamba") * 20)
    with pytest.raises(NotImplementedError):
        Lfm2MoeModel(cfg).apply(None, None)


# ------------------------------------------------------------------ the engine


def _ref_greedy(cfg, params, prompt, n_new, buf=192):
    """Greedy tokens from the reference's full forward, no cache, no window:
    the sequence lives in a buffer of one length (a causal forward's logits do
    not see what follows a position)."""
    layers = ref_mod.to_layers(params, cfg.layer_kinds, cfg.n_dense_layers)
    seq = np.zeros(buf, np.int32)
    seq[: len(prompt)] = prompt
    for i in range(len(prompt), len(prompt) + n_new):
        logits = ref_mod.forward(layers, jnp.asarray(seq), **driver.reference_kwargs(cfg)).logits
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


PROMPTS = [[5, 7, 9], list(PROMPT[:40]), list(PROMPT) + list(PROMPT[:30])]  # one, two and three chunks, none a whole number of them


def test_the_engine_serves_a_fleet_of_mixed_lengths_on_one_shape_each(served):
    cfg, llm, eng = served
    outs = [r.sink.result(timeout=300) for r in [eng.submit(list(map(int, p)), 5) for p in PROMPTS]]
    for p, o in zip(PROMPTS, outs):
        assert o == _ref_greedy(cfg, llm.params, p, 5)  # the reference: no cache, no chunks, no pool, no window
    assert eng.compile_stats() == {"prefill": 1, "decode": 1}


def test_a_row_is_the_same_alone_and_among_others_and_a_reused_slot_starts_clean(served):
    """Each prompt again, alone, on slot 0 -- whose windows the request before
    it left full of its own z: the same tokens as in the fleet, and as a fresh
    engine's."""
    cfg, llm, eng = served
    want = [_ref_greedy(cfg, llm.params, p, 5) for p in PROMPTS]
    assert np.asarray(eng._pages[3])[:, 0].any()  # slot 0's windows are not zero when the next request takes it
    for p, w in zip(PROMPTS[::-1], want[::-1]):
        assert eng.submit(list(map(int, p)), 5).sink.result(timeout=300) == w
    assert eng.compile_stats() == {"prefill": 1, "decode": 1}
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    fresh = InferenceEngine(llm, EngineConfig(num_slots=1, page_size=PAGE, max_seq_len=192, prefill_chunk=CHUNK, max_new_tokens=5), deployment="f")
    try:
        assert fresh.submit(list(map(int, PROMPTS[1])), 5).sink.result(timeout=300) == want[1]
    finally:
        fresh.shutdown()


def test_defrag_moves_pages_and_leaves_windows_in_place(served):
    cfg, llm, eng = served
    want = _ref_greedy(cfg, llm.params, PROMPTS[2], 5)
    first = eng.submit(list(map(int, PROMPTS[0])), 5)  # takes the lowest pages, then frees them
    second = eng.submit(list(map(int, PROMPTS[2])), 5)
    first.sink.result(timeout=300)
    moved = eng.defrag()
    assert second.sink.result(timeout=300) == want and "moves" in moved
    # at rest: a compaction copies the two pages members and hands the counter and the windows on as they are
    counter, windows = np.asarray(eng._pages[2]), np.asarray(eng._pages[3])
    eng.defrag()
    assert np.array_equal(counter, np.asarray(eng._pages[2])) and np.array_equal(windows, np.asarray(eng._pages[3]))
    assert eng.compile_stats() == {"prefill": 1, "decode": 1}


def test_the_pool_is_pages_for_the_attending_layers_and_two_rows_a_slot_a_conv_layer(served):
    import time

    cfg, llm, eng = served
    roles, pool = llm.model.pool_roles(), eng._pages
    assert roles == ("pages", "pages", "counter", "state") and len(pool) == 4
    n_attn, n_conv = cfg.layer_kinds.count("attn"), cfg.layer_kinds.count("conv")
    assert (n_attn, n_conv) == (2, 4)
    assert pool[0].shape == pool[1].shape == (n_attn, eng.cfg.pool_pages(), PAGE, cfg.n_kv_heads * cfg.head_dim)  # no conv layer has pages; a position is one row
    assert pool[3].shape == (n_conv, 3, cfg.conv_kernel - 1, cfg.dim) and pool[3].dtype == cfg.compute_dtype  # no attention layer a window
    steps, resets = eng.stats()["decode_steps"], eng.stats()["state_resets"]
    eng.submit([3, 4, 5, 6], 3).sink.result(timeout=300)
    eng._wake.set()
    time.sleep(0.3)  # an idle tick publishes
    st = eng.stats()
    assert st["cache_bytes_per_position"] == 2 * n_attn * cfg.n_kv_heads * cfg.head_dim * 4  # float32 here: K and V of the two attending layers
    assert st["state_bytes_per_slot"] == n_conv * 2 * cfg.dim * 4 and st["state_bytes"] == 3 * st["state_bytes_per_slot"]
    assert st["decode_steps"] - steps == 2 and st["state_resets"] - resets == 1
    assert len(st["moe_expert_load"]) == cfg.n_experts and st["moe_assignments_seen"] % ((cfg.n_layers - 2) * cfg.n_experts_per_tok) == 0
    # at the published widths and ten layers: 4 KiB a position, 64 KiB a slot
    pub = Lfm2MoeConfig(n_layers=10)
    assert 2 * pub.layer_kinds.count("attn") * pub.n_kv_heads * pub.head_dim * 2 == 4096 and pub.layer_kinds.count("conv") * 2 * pub.dim * 2 == 65_536


def test_a_model_without_state_reports_no_state_bytes_per_slot():
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = LlamaConfig.tiny(compute_dtype=jnp.bfloat16)
    eng = InferenceEngine(ShardedLLM(cfg, tp=1), EngineConfig(num_slots=2, page_size=8, max_seq_len=64, prefill_chunk=16), deployment="d")
    try:
        assert "state_bytes_per_slot" not in eng.stats() and "state_bytes" not in eng.stats()
    finally:
        eng.shutdown()
    # Jamba2's and Qwen3-Next's at their published widths, a slot: 26 Mamba layers of state and window; 6 of 8 layers of matrix state and window
    j = JambaConfig()
    assert 26 * (16 * j.d_inner * 4 + 3 * j.d_inner * 2) == 9_318_400
