"""A serving cell of a short-convolution expert model (``kind:
serve_conv_moe``: lfm2_moe keys, LFM2-24B-A2B): gated short-convolution
mixers that keep a window of ``conv_L_cache - 1`` rows a slot and no pages,
grouped-query attention with a per-head QK-norm in the rest of the layers,
leading dense layers, then routed experts under a sigmoid router with a
selection bias.  The same ``serve.run(engine_llm_deployment(...))`` replica,
window and judgement as ``drivers/serve.py``, through ``drivers/serve_moe.py``'s
``run`` (client, routing counters) and ``drivers/serve_mla_moe.py``'s (the live
positions of the decode steps between the replies nearest the capture).

How it is put in without editing any of those files: ``serve_mla_moe.run``
looks up ``mla_config`` and ``reference_check`` as globals of its module when
it executes and hands them on (``serve_mla_moe._as_the_expert_kind``, then
``serve_moe.substituted``), so ``run`` below binds this file's two for the
length of the call.  The configuration is built FIRST: a program without the
model (this PR's parent) raises ``ImportError`` there, before a replica or a
TPU worker exists.

The comparison with ``reference/lfm2_ref.py`` (traced runs only) and its
tolerances are below.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Mapping

from benchmarks.drivers import serve_mla_moe, serve_moe
from benchmarks.drivers import serve_qwen3_next as hybrid
from benchmarks.drivers.serve import LOGIT_TOL
from benchmarks.drivers.serve_mla_moe import _rel, fp8_weights
from benchmarks.drivers.serve_moe import ROUTER_TOL
from benchmarks.drivers.serve_qwen3_next import DECODE_STEPS, SLOT, SLOTS


def conv_config(cfg: Mapping):
    """The program's ``Lfm2MoeConfig`` for a configuration file with the
    published lfm2_moe keys."""
    import jax.numpy as jnp

    from ray_tpu.models.lfm2 import Lfm2MoeConfig

    rope = cfg["rope_parameters"]
    if cfg.get("conv_bias") or rope.get("rope_type", "default") != "default" or cfg.get("tie_embedding") is False or not cfg["use_expert_bias"]:
        raise ValueError("the program's block has no conv bias and no rope scaling, its head is the embedding, and its router has a selection bias")
    if len(cfg["layer_types"]) < cfg["num_hidden_layers"] or cfg["hidden_size"] % cfg["num_attention_heads"]:
        raise ValueError("layer_types names every layer (the model is its first num_hidden_layers entries), and a head is hidden_size / num_attention_heads wide")
    dtype = jnp.dtype(cfg["torch_dtype"])
    return Lfm2MoeConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"], layer_types=tuple(cfg["layer_types"]),
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], conv_kernel=cfg["conv_L_cache"],
        hidden_dim=cfg["moe_intermediate_size"], dense_hidden_dim=cfg["intermediate_size"], n_dense_layers=cfg["num_dense_layers"],
        n_experts=cfg["num_experts"], n_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]), norm_topk_prob=bool(cfg["norm_topk_prob"]),
        max_seq_len=cfg["engine"]["max_seq_len"], rope_theta=float(rope["rope_theta"]), norm_eps=cfg["norm_eps"], compute_dtype=dtype, param_dtype=dtype,
    )


# ---- the comparison with the reference
#
# One prompt that spans two engine chunks with a ragged tail, then DECODE_STEPS
# decode steps, through the replica's own two programs
# (``llm.engine_programs``: pool donated, one compile each) on a two-slot pool
# whose slot 1 owns the pool's first pages in reverse order and held a short
# other prompt before, so that its windows are not zero when the prompt's first
# chunk arrives and must be reset by it (``serve_qwen3_next.run_paged``, as it
# stands).  Against the reference's one full forward over prompt + generated
# tokens -- no cache, no windows: a padded causal convolution over the whole
# sequence -- GIVEN THE PROGRAM'S ROUTING (``drivers/serve_moe.py``, for its
# reason: a top-k is discrete): the attending layers' K/V pages layer by layer,
# the conv layers' windows against the reference's z = B * u at the LAST TWO
# positions, the greedy tokens' logits, the choices where the reference's own
# margin is clear, and the routing counter through ``serve_moe.judge_copies``,
# handed the SELECTION scores sigma + b, whose 4th and 5th a choice lies
# between.  The idle slot's windows must still be zero.
#
# The weights are ``ShardedLLM``'s from ``--seed`` with every norm scale
# (block, head, final) multiplied by 1 + N(0, 0.2) drawn from the seed: at the
# initial value 1 a head norm applied AFTER rotary equals one applied before
# (the RMS of a head is the same either way; only the scale does not commute
# with the rotation), and a comparison could not tell them apart.
#
# The whole-program readings see a wrong projection, tap or norm in what the
# cache and the windows keep.  They cannot see what only weighs a layer's
# OUTPUT at these weights, nor the PRECISION of the router or of a norm (the
# bf16 matmuls around them already put the error at 0.5%), and a window
# mishandled at a chunk's edge moves two rows of hundreds.  So six checks
# isolate a part each, the program's own function on the REFERENCE'S inputs,
# and every traced run tries on them the departures they must refuse
# (``DEPARTURES``; each has to come out not ok):
#
# - the norm alone (NORM_TOL): the model file's RMSNorm on the reference's
#   float32 residual stream;
# - the router alone (``serve_moe``'s ROUTER_TOL): ``parallel/moe.route_sigmoid``
#   with the bias, on the reference's router inputs, returns the reference's
#   scores of its chosen experts, and its choices wherever the selection
#   margin is sure;
# - the expert layer alone (FFN_TOL): the model's ``_ffn`` of the first expert
#   layer on the reference's residual stream, against the reference's routed
#   sum following the choices that call made;
# - a leading dense layer alone (FFN_TOL): the model's ``_dense_ffn`` of layer 0;
# - the conv mixer alone (CONV_TOL RMS, CONV_MAX_TOL worst element,
#   CONV_WINDOW_TOL its window): the model's ``_short_conv`` of the first conv layer
#   through a windows member of its own whose rows start NOT zero, the same
#   chunks (the last padded) then one row a step, on the reference's normed
#   rows, against the reference's mixer and its z;
# - the attention mixer alone (ATTN_TOL, and the K/V limits): the model's
#   ``_attn`` of the first attending layer through a small pool, likewise.
#
# Every limit lies between two readings on the chip at the published widths,
# both of the tree as committed (PERF.md section 6, PR 47): what the bf16
# program gives over its seeds (the check inside the traced runs,
# ``chiprun_out/pr47/*_t1.detail.json`` and ``refcheck_bf16_*``), and what it
# gives with its weights rounded to fp8 (``serve_mla_moe.fp8_weights``: e4m3,
# the nearest precision below the configuration's bf16;
# ``_reference_check_in_worker(control=True)``, ``refcheck_fp8_*``), which must
# come out not ok, by several limits -- as it does at the tiny size in
# ``tests/test_lfm2.py``.
#
# | what                                     | bf16 program    | fp8 weights | limit |
# | K/V of the attending layer, RMS          | 0.78-0.81%      | 12.4-12.6%  | KV_REL_TOL 3% |
# | K/V, worst element                       | 3.4-5.3%        | 59-77%      | KV_MAX_TOL 15% |
# | conv windows (z, last two rows), RMS, worst layer (the fourth) | 1.15-1.18% | 16.8% | WINDOW_REL_TOL 4% |
# | greedy token's logit under the best      | 0.0             | 0.24        | LOGIT_TOL 0.08 (``serve.py``'s: 9% of the logits' deviation of 0.93) |
# | widest routing flip (relative margin)    | 0.0030-0.0064   | 0.110, 116 flips above the limit | MARGIN 0.02 |
# | conv mixer alone, its output, RMS / worst| 0.50% / 2.7-3.3% | 6.0% / 33%  | CONV_TOL 1.5% / CONV_MAX_TOL 10% |
# | conv mixer alone, its window, RMS        | 0.35-0.38%      | 3.8%        | CONV_WINDOW_TOL 1.2% |
# | attention mixer alone, its output, RMS   | 0.49-0.50%      | 5.9%        | ATTN_TOL 1.5% |
# | attention mixer alone, its K/V, RMS / worst | 0.29% / 2.2-2.6% | 3.7% / 26% | the K/V limits above |
# | expert layer alone, its output, RMS      | 0.39%           | 6.2%        | FFN_TOL 1.5% |
# | dense layer alone, its output, RMS       | 0.35%           | 6.2%        | FFN_TOL 1.5% |
# | the norm alone, worst element            | 1.4e-6          | (a bf16 norm: 3.8-4.3%) | NORM_TOL 1e-4 |
# | the router alone, weights                | 0.0             | (a bf16 router: 3.6-3.7e-3) | ROUTER_TOL 1e-4 (``serve_moe``'s) |
#
# (RMS and worst element are over the RMS of the reference's tensor; my chip
# runs, PR 47: three seeds in bf16 -- ``a_refcheck_bf16_s4700000011``,
# ``b_refcheck_bf16_s4700000012`` and the check inside the traced run
# ``b_*_s4700000102_t1`` --, one in fp8, ``b_refcheck_fp8_s4700000011``;
# four layers: both dense conv layers, the attending and a conv layer with
# experts.)  The planted departures read, on the chip: a window not carried
# across a chunk's edge or taken at its padded end moves TWO rows of 354 --
# 5.0-5.5% RMS of the mixer's output, its worst element 2.5-3.3 times the
# output's RMS, so CONV_MAX_TOL is the limit that refuses them with room; a
# tap left out 57%, the taps reversed 115%, silu after them 50%, B and C
# swapped 115% (and the window 139%), C left out 149%; no head norm 28%, the
# head norm after rotary 14-17%; a dense layer run as an expert layer 102%;
# no renormalisation 200%; the bias left out 690 flipped rows of 692, the bias
# as a weight 245-275%.  In 99.7-99.9% of rows the chosen set is not the raw
# scores' top 4 (``bias_decides_share``): the selection bias, drawn at the
# size of the scores' own spread, decides.  A quarter of the rows are near
# ties under MARGIN (``near_tie_share`` 0.25-0.30).
KV_REL_TOL = 0.03
KV_MAX_TOL = 0.15
WINDOW_REL_TOL = 0.04
MARGIN = 0.02
CONV_TOL = 0.015
CONV_MAX_TOL = 0.10
CONV_WINDOW_TOL = 0.012
ATTN_TOL = 0.015
FFN_TOL = 0.015
NORM_TOL = 1e-4
NORM_SCALE_SPREAD = 0.2


def reference_kwargs(c) -> dict:
    """What ``lfm2_ref.forward`` is told of an ``Lfm2MoeConfig``."""
    return dict(n_heads=c.n_heads, n_kv_heads=c.n_kv_heads, rope_theta=c.rope_theta, eps=c.norm_eps, top_k=c.n_experts_per_tok,
                norm_topk_prob=c.norm_topk_prob, routed_scaling_factor=c.routed_scaling_factor)


def mixer_alone(model, kind: str, wp, h, *, page: int, chunk: int, prefill_len: int):
    """The model's mixer of ONE layer (``kind`` "conv" or "attn", weights
    ``wp``) on given normed rows h [S, E], through a pool of its own as the two
    paged programs drive it: the first ``prefill_len`` rows in chunks of
    ``chunk`` (the last padded), the rest one row a call on slot SLOT of
    SLOTS.  A conv layer's windows start as ONES in both slots, so that the
    first chunk has something to reset and the idle slot something to keep.
    Returns (what the mixer adds [S, E], what it kept: the window [k - 1, E]
    and whether the idle slot's still is ones, or the pool's (keys, values)
    [S, KV, D])."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    S = h.shape[0]
    per_slot = (S + page) // page + 1
    n_pages = SLOTS * per_slot
    tables = np.full((SLOTS, per_slot), -1, np.int32)
    tables[SLOT] = np.arange(per_slot, dtype=np.int32)[::-1]
    kp, vp, _, windows = model.init_pages(n_pages, page, SLOTS)
    kept = jnp.ones_like(windows[:1]) if kind == "conv" else (kp[:1], vp[:1])

    def call(kept, wp, hs, tabs, pos, valid, slot=None):
        """hs [B, Q, E] at positions pos [B, Q] of the slots whose tables are tabs [B, MP]; the weights are an argument, not a constant of the executable."""
        if kind == "conv":
            return model._short_conv(hs, wp, 0, kept, slot, pos, valid)
        wpage = jnp.take_along_axis(tabs, pos // page, axis=1)
        wpage = jnp.where(valid & (wpage >= 0), wpage, n_pages).reshape(-1)
        blocks, n_blocks = model._walk_blocks(tabs, page, pos, valid)
        return model._attn(hs, wp, 0, kept, wpage, (pos % page).reshape(-1), blocks, pos, valid, n_blocks)

    call = jax.jit(call)
    h = jnp.asarray(h).astype(model.config.compute_dtype)
    outs = []
    for start in range(0, prefill_len, chunk):
        n = min(chunk, prefill_len - start)
        hs = jnp.zeros((1, chunk, h.shape[1]), h.dtype).at[0, :n].set(h[start : start + n])
        out, kept = call(kept, wp, hs, tables[SLOT : SLOT + 1], (start + np.arange(chunk, dtype=np.int32))[None], (np.arange(chunk) < n)[None], np.int32(SLOT))
        outs.append(out[0, :n])
    for t in range(prefill_len, S):
        hs = jnp.zeros((SLOTS, 1, h.shape[1]), h.dtype).at[SLOT, 0].set(h[t])
        pos, valid = np.zeros((SLOTS, 1), np.int32), np.zeros((SLOTS, 1), bool)
        pos[SLOT], valid[SLOT] = t, True
        out, kept = call(kept, wp, hs, tables, pos, valid)
        outs.append(out[SLOT])
    out = jnp.concatenate(outs).astype(jnp.float32)
    if kind == "conv":
        win = np.asarray(kept.astype(jnp.float32))[0]
        return out, (win[SLOT], bool((win[1 - SLOT] == 1.0).all()))
    at = np.arange(S)
    cfg = model.config
    return out, tuple(np.asarray(m.astype(jnp.float32))[0, tables[SLOT][at // page], at % page].reshape(S, cfg.n_kv_heads, cfg.head_dim) for m in kept)


def compare(llm, prompt, *, page: int, chunk: int, ref_params=None, departures=(),
            kv_tol=KV_REL_TOL, kv_max_tol=KV_MAX_TOL, window_tol=WINDOW_REL_TOL, logit_tol=LOGIT_TOL, margin=MARGIN, router_tol=ROUTER_TOL,
            conv_tol=CONV_TOL, conv_max_tol=CONV_MAX_TOL, conv_window_tol=CONV_WINDOW_TOL, attn_tol=ATTN_TOL, ffn_tol=FFN_TOL, norm_tol=NORM_TOL) -> dict:
    """The program (``llm``: a ``ShardedLLM`` of an ``Lfm2MoeConfig``) against
    ``lfm2_ref`` on one prompt.  The reference reads ``ref_params`` (default:
    the program's own weights) and ``llm.cfg``.  Each of ``departures``
    (``DEPARTURES``' names) is planted and tried on the one check alone that
    sees it, and reported under its name with that check's ``ok``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import lfm2_ref as ref_mod
    from ray_tpu.models import lfm2
    from ray_tpu.parallel import moe

    c = llm.cfg
    kinds = c.layer_kinds
    top_k, plen, n_dense, n_moe = c.n_experts_per_tok, len(prompt), c.n_dense_layers, c.n_layers - c.n_dense_layers
    num_pages = hybrid.pool_pages(plen, page)
    kw = dict(page=page, chunk=chunk, vocab=c.vocab_size)
    tokens, pool, table, _, _ = hybrid.run_paged(llm.engine_programs(num_pages=num_pages, page_size=page, num_slots=SLOTS), llm.params, prompt, **kw)
    copy_tokens, copy_pool, _, routing, tenant = hybrid.run_paged(hybrid.routing_programs(llm, num_pages, page), llm.params, prompt, **kw)
    copy_differs = copy_tokens != tokens or not all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(pool, copy_pool))
    full = jnp.asarray(np.concatenate([prompt, tokens[:-1]]).astype(np.int32))  # every token that was fed
    rows = plen + DECODE_STEPS

    own = ref_params if ref_params is not None else llm.params
    layers = ref_mod.to_layers(own, kinds, n_dense)
    ref = ref_mod.forward(layers, full, routing=jnp.asarray(routing), **reference_kwargs(c))
    select = np.asarray(ref.select)
    order, rel_margin = serve_moe.rank_router(select, top_k)  # rel_margin [L_moe, rows]
    clear = rel_margin > margin
    agree = (np.sort(routing, -1) == np.sort(order[..., :top_k], -1)).all(-1)
    raw_order, _ = serve_moe.rank_router(np.asarray(ref.scores), top_k)
    bias_decides = (np.sort(raw_order[..., :top_k], -1) != np.sort(order[..., :top_k], -1)).any(-1)  # rows whose chosen set is not the raw scores' top-k

    pos = np.arange(rows)
    where = (slice(None), table[pos // page], pos % page)  # of a K/V member [L_attn, pages, page, ...]: what the rows wrote, [L_attn, rows, ...]
    written = lambda member: np.asarray(member.astype(jnp.float32))[where]  # noqa: E731
    heads = lambda rows_: rows_.reshape(*rows_.shape[:-1], c.n_kv_heads, c.head_dim)  # noqa: E731 -- a position's row is its KV heads one after the other
    got_k, got_v = heads(written(pool[0])), heads(written(pool[1]))
    (k_rms, k_max), (v_rms, v_max) = _rel(got_k, ref.keys), _rel(got_v, ref.values)
    windows = np.asarray(pool[3].astype(jnp.float32))  # [L_conv, slots, k - 1, E]
    keep = c.conv_kernel - 1
    want_windows = np.asarray(ref.z)[:, rows - keep : rows]  # z at the last k - 1 positions that were fed
    per_layer = [_rel(windows[i, SLOT], want_windows[i])[0] for i in range(windows.shape[0])]
    w_rms = max(per_layer)
    idle_touched = bool(np.any(windows[:, 1 - SLOT]))
    logits = np.asarray(ref.logits, np.float32)[:, : c.vocab_size]
    gaps = [float(logits[plen - 1 + j].max() - logits[plen - 1 + j, tok]) for j, tok in enumerate(tokens)]

    # ---- a part alone, on the reference's inputs, as the program's modules and ``llm.model`` have it when called
    first = {kind: kinds.index(kind) for kind in ("conv", "attn")}  # the first layer of each kind
    with jax.default_matmul_precision("highest"):
        normed = lambda x, w: ref_mod._norm(jnp.asarray(x), jnp.asarray(w, jnp.float32), c.norm_eps)  # noqa: E731
        router_in = [normed(ref.ffn_in[n_dense + i], own["moe"]["ffn_norm"][i]) for i in range(n_moe)]
        mixer_h = {kind: normed(ref.mixer_in[i], layers["layers"][i]["op_norm"]) for kind, i in first.items()}

    def norm_alone():
        x, w = jnp.asarray(ref.mixer_in[-1]), jnp.asarray(layers["layers"][-1]["op_norm"], jnp.float32)
        got = jax.jit(lambda x, w: lfm2._rms_norm(x, w, c.norm_eps))(x, w)
        return {"norm_alone_err": _rel(got, ref_mod._norm(x, w, c.norm_eps))[1]}

    def router_alone():
        err, flips = 0.0, 0
        for i in range(n_moe):
            h, wr, b = router_in[i].astype(c.compute_dtype), own["moe"]["router"][i], own["moe"]["router_bias"][i]
            w_prog, c_prog = (np.asarray(a) for a in jax.jit(lambda h, w, b: moe.route_sigmoid(h, w, top_k, b))(h, wr, b))
            sigma, sel, c_ref = (np.asarray(a) for a in ref_mod.route(h.astype(jnp.float32), wr, b, top_k))
            err = max(err, float(np.abs(w_prog / np.take_along_axis(sigma, c_prog, -1) - 1.0).max()))
            _, sure = serve_moe.rank_router(sel, top_k)
            flips += int(((np.sort(c_prog, -1) != np.sort(c_ref, -1)).any(-1) & (sure > 10 * router_tol)).sum())
        return {"router_weight_err": err, "router_flips": flips}

    def ffn_alone():
        model, mp = llm.model, jax.tree.map(lambda a: a[0], llm.params["moe"])
        moe32 = jax.tree.map(lambda a: jnp.asarray(a[0], jnp.float32), own["moe"])
        # the residual stream in float32: the layer rounds its normed input to the compute type itself, and x + y is
        # then a float32 sum, so that out - x is what the layer added and not that rounded to x's last bit
        x = jnp.asarray(ref.ffn_in[n_dense])[None]
        out, chosen = jax.jit(lambda x, mp: model._ffn(x, mp))(x, mp)  # a function of its own: nothing traced before is found again

        @jax.jit
        def want(x, chosen, moe32):
            with jax.default_matmul_precision("highest"):
                g = ref_mod._norm(x, moe32["ffn_norm"], c.norm_eps)
                sigma, _, _ = ref_mod.route(g, moe32["router"], moe32["router_bias"], top_k)
                weight = ref_mod.routed_weights(sigma, chosen, norm_topk_prob=c.norm_topk_prob, routed_scaling_factor=c.routed_scaling_factor)
                return ref_mod.expert_ffn(g, weight, chosen, moe32["w_gate"], moe32["w_up"], moe32["w_down"])

        return {"ffn_alone_err": _rel((out - x)[0], want(x[0], chosen[0], moe32))[0]}

    def dense_alone():
        if not n_dense:
            return {}
        model, fp = llm.model, jax.tree.map(lambda a: a[0], llm.params["dense"])
        x = jnp.asarray(ref.ffn_in[0])[None]
        out = jax.jit(lambda x, fp: model._dense_ffn(x, fp))(x, fp)
        return {"dense_alone_err": _rel((out - x)[0], ref.ffn_out[0])[0]}

    def conv_alone():
        cp = jax.tree.map(lambda a: a[0], llm.params["conv"])
        out, (win, idle_kept) = mixer_alone(llm.model, "conv", cp, mixer_h["conv"], page=page, chunk=chunk, prefill_len=plen)
        o_rms, o_max = _rel(out, ref.mixer_out[first["conv"]])
        return {"conv_alone_err": o_rms, "conv_alone_max_err": o_max, "conv_alone_window_err": _rel(win, want_windows[0])[0], "conv_alone_idle_window_moved": not idle_kept}

    def attn_alone():
        ap = jax.tree.map(lambda a: a[0], llm.params["attn"])
        out, (keys, values) = mixer_alone(llm.model, "attn", ap, mixer_h["attn"], page=page, chunk=chunk, prefill_len=plen)
        (k_r, k_m), (v_r, v_m) = _rel(keys, ref.keys[0]), _rel(values, ref.values[0])
        return {"attn_alone_err": _rel(out, ref.mixer_out[first["attn"]])[0], "attn_alone_kv_err": max(k_r, v_r), "attn_alone_kv_max_err": max(k_m, v_m)}

    checks = {"norm": norm_alone, "router": router_alone, "ffn": ffn_alone, "dense": dense_alone, "conv": conv_alone, "attn": attn_alone}

    def passes(found: dict) -> bool:
        limits = {"norm_alone_err": norm_tol, "router_weight_err": router_tol, "router_flips": 0, "ffn_alone_err": ffn_tol, "dense_alone_err": ffn_tol,
                  "conv_alone_err": conv_tol, "conv_alone_max_err": conv_max_tol, "conv_alone_window_err": conv_window_tol, "conv_alone_idle_window_moved": False,
                  "attn_alone_err": attn_tol, "attn_alone_kv_err": kv_tol, "attn_alone_kv_max_err": kv_max_tol}
        return all(found[k] <= limits[k] for k in found)

    found = {k: v for check in checks.values() for k, v in check().items()}
    narrowed = {}
    for which in departures:
        with departure(which, llm):
            seen = checks[DEPARTURES[which]]()
        narrowed[which] = {**seen, "ok": passes(seen)}

    load = np.asarray(pool[2]).astype(np.int64)
    counted = np.bincount(np.concatenate([routing.reshape(-1), tenant.reshape(-1)]), minlength=c.n_experts)  # the slot's earlier tenant too
    tenant_select = ref_mod.forward(layers, jnp.asarray(hybrid.tenant_tokens(chunk, c.vocab_size)), routing=jnp.asarray(tenant), **reference_kwargs(c)).select
    copy_windows = np.asarray(copy_pool[3].astype(jnp.float32))
    copies = serve_moe.judge_copies(
        select, routing, load, top_k=top_k, margin=margin, expected_total=(rows + tenant.shape[1]) * n_moe * top_k,
        tokens=tokens, copy_tokens=copy_tokens, token_rows=range(plen - 1, rows), aside=[(np.asarray(tenant_select), tenant)],
        pieces=[("keys", got_k, heads(written(copy_pool[0])), kv_tol, kv_max_tol), ("values", got_v, heads(written(copy_pool[1])), kv_tol, kv_max_tol),
                ("windows", windows[:, SLOT], copy_windows[:, SLOT], window_tol, float("inf"))],
        rest_equal=serve_moe.equal_outside(pool[0], copy_pool[0], where) and serve_moe.equal_outside(pool[1], copy_pool[1], where)
        and np.array_equal(windows[:, 1 - SLOT], copy_windows[:, 1 - SLOT]),
    )
    out = {
        "layers": c.n_layers, "layer_kinds": "".join(kind[0] for kind in kinds), "dense_layers": n_dense, "prompt_len": int(plen), "decode_steps": DECODE_STEPS,
        "chunks": -(-plen // chunk), "experts": c.n_experts, "top_k": top_k, "pool_roles": list(llm.model.pool_roles()),
        "window_shape": list(pool[3].shape), "window_dtype": str(pool[3].dtype), "kv_layers": int(pool[0].shape[0]), "kv_row_dim": int(pool[0].shape[-1]),
        "k_rel_err": k_rms, "v_rel_err": v_rms, "k_max_err": k_max, "v_max_err": v_max,
        "window_rel_err": w_rms, "window_rel_err_by_layer": per_layer, "idle_slot_touched": idle_touched,
        "logit_gap_max": max(gaps), "logit_std": float(logits[rows - 1].std()),
        "routing_agreement": float(agree.mean()), "routing_flips_above_margin": int((clear & ~agree).sum()),
        "near_tie_share": float(1.0 - clear.mean()), "flipped_margin_max": float(rel_margin[~agree].max()) if (~agree).any() else 0.0,
        "bias_decides_share": float(bias_decides.mean()), **found,
        "routing_copy_differs": bool(copy_differs), "routing_copy_flips": copies["flips"], "routing_copy_problems": copies["problems"],
        "routing_copy_flip_margin": copies["flip_margin"],
        "moe_load_total": int(load.sum()), "moe_load_miscount": int(np.abs(load - counted).sum()),
        "kv_tol": kv_tol, "kv_max_tol": kv_max_tol, "window_tol": window_tol, "logit_tol": logit_tol, "margin": margin, "router_tol": router_tol,
        "conv_tol": conv_tol, "conv_max_tol": conv_max_tol, "conv_window_tol": conv_window_tol, "attn_tol": attn_tol, "ffn_tol": ffn_tol, "norm_tol": norm_tol,
        "platform": jax.devices()[0].platform, **narrowed,
    }
    out["ok"] = bool(
        max(k_rms, v_rms) <= kv_tol and max(k_max, v_max) <= kv_max_tol and w_rms <= window_tol and not idle_touched
        and max(gaps) <= logit_tol and out["routing_flips_above_margin"] == 0
        and out["pool_roles"] == ["pages", "pages", "counter", "state"] and out["kv_layers"] == kinds.count("attn") and out["kv_row_dim"] == c.n_kv_heads * c.head_dim
        and out["window_shape"] == [kinds.count("conv"), SLOTS, keep, c.dim] and out["window_dtype"] == str(jnp.dtype(c.compute_dtype))
        and passes(found) and copies["ok"]
    )
    return out


# ---- what the tolerances must refuse: a name -> the check alone that sees it
DEPARTURES = {
    "bf16_norm": "norm", "bf16_router": "router", "no_bias": "router", "bias_as_weight": "router",
    "no_renorm": "ffn", "dense_as_expert": "dense",
    "tap_left_out": "conv", "taps_reversed": "conv", "silu_after_conv": "conv", "b_c_swapped": "conv", "c_left_out": "conv",
    "window_not_carried": "conv", "window_at_padded_end": "conv",
    "no_head_norm": "attn", "head_norm_after_rotary": "attn",
}


@contextlib.contextmanager
def departure(which: str, llm):
    """The program with one thing planted, for the length of the block: what
    is traced inside has it, what was traced before does not.  ``llm.cfg``
    (what the reference is told) stays as published; what changes is a
    function the program looks up in its module when traced (the model file's
    norm and window functions, ``moe.route_sigmoid``), a method of
    ``llm.model`` (an instance attribute in front of the class's), its
    configuration, or ``llm.params``.

    bf16_norm / bf16_router: float32 where the configuration says so, narrowed;
    no_bias: the top-k of the raw scores; bias_as_weight: the weights are
    scores + bias; no_renorm: the raw scores as weights; dense_as_expert: a
    leading layer's FFN is an expert layer's (the first one's weights); tap_left_out: w_0 = 0
    (the convolution is two taps long); taps_reversed; silu_after_conv:
    Jamba's form; b_c_swapped / c_left_out: in_proj's blocks read [C | B | u]
    / C = 1; window_not_carried: every chunk starts from a zero window;
    window_at_padded_end: the window after a chunk is its last k - 1 rows,
    padding included; no_head_norm; head_norm_after_rotary."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import lfm2
    from ray_tpu.parallel import moe

    model, c = llm.model, llm.model.config
    real = {"norm": lfm2._rms_norm, "route": moe.route_sigmoid, "taps": lfm2.conv_window_taps, "after": lfm2.conv_window_after,
            "in_proj": model._in_proj, "ffn": model._ffn}
    to_bf16 = lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)  # noqa: E731 -- not a cast there and back, which XLA may drop

    def bf16_norm(x, scale, eps):
        x = to_bf16(x.astype(jnp.float32))
        return to_bf16(to_bf16(x * to_bf16(jax.lax.rsqrt(to_bf16((x**2).mean(-1, keepdims=True)) + eps))) * scale)

    def bf16_route(h, router_w, top_k, bias):
        scores = jax.nn.sigmoid(h.astype(jnp.bfloat16) @ router_w.astype(jnp.bfloat16))
        _, chosen = jax.lax.top_k(scores.astype(jnp.float32) + bias.astype(jnp.float32), top_k)
        return jnp.take_along_axis(scores, chosen, -1).astype(jnp.float32), chosen

    def bias_as_weight(h, router_w, top_k, bias):
        weights, chosen = real["route"](h, router_w, top_k, bias)
        return weights + bias.astype(jnp.float32)[chosen], chosen

    def silu_taps(*a, **kw):
        y, seq, fresh = real["taps"](*a, **kw)
        return jax.nn.silu(y), seq, fresh

    def never_carried(member, li, slot, u, w, q_pos, q_valid, *a, **kw):
        chunk_call = u.shape[1] > 1
        return real["taps"](member, li, slot, u, w, jnp.zeros_like(q_pos) if chunk_call else q_pos, jnp.ones_like(q_valid) if chunk_call else q_valid, *a, **kw)

    def at_padded_end(member, li, slot, seq, q_valid):
        return real["after"](member, li, slot, seq, jnp.ones_like(q_valid) if q_valid.shape[1] > 1 else q_valid)

    def swapped(h, cp):
        B, C, u = real["in_proj"](h, cp)
        return C, B, u

    def no_c(h, cp):
        B, C, u = real["in_proj"](h, cp)
        return B, jnp.ones_like(C), u

    def rope_only(x, w, positions):
        return lfm2._partial_rope(x, positions, c.rope_theta, c.head_dim)

    def norm_after_rope(x, w, positions):
        return real["norm"](rope_only(x, w, positions), w.astype(jnp.float32), c.norm_eps).astype(c.compute_dtype)

    modules = {"bf16_norm": (lfm2, "_rms_norm", bf16_norm), "bf16_router": (moe, "route_sigmoid", bf16_route), "bias_as_weight": (moe, "route_sigmoid", bias_as_weight),
               "no_bias": (moe, "route_sigmoid", lambda h, w, k, bias: real["route"](h, w, k)),
               "silu_after_conv": (lfm2, "conv_window_taps", silu_taps), "window_not_carried": (lfm2, "conv_window_taps", never_carried),
               "window_at_padded_end": (lfm2, "conv_window_after", at_padded_end)}
    methods = {"b_c_swapped": ("_in_proj", swapped), "c_left_out": ("_in_proj", no_c), "no_head_norm": ("_head_norm_rope", rope_only),
               "head_norm_after_rotary": ("_head_norm_rope", norm_after_rope), "dense_as_expert": ("_dense_ffn", lambda x, fp: real["ffn"](x, fp)[0])}
    configs = {"no_renorm": dict(norm_topk_prob=False)}
    taps = {"tap_left_out": lambda w: w.at[:, 0].set(0), "taps_reversed": lambda w: w[:, ::-1]}
    params = llm.params
    try:
        if which in modules:
            mod, name, fn = modules[which]
            setattr(mod, name, fn)
        elif which in methods:
            setattr(model, *methods[which])
            if which == "dense_as_expert":  # the leading layers' FFN weights ARE the expert layers': arguments of the call, as a layer's weights always are
                llm.params = {**params, "dense": params["moe"]}
        elif which in configs:
            model.config = dataclasses.replace(c, **configs[which])
        elif which in taps:
            llm.params = {**params, "conv": {**params["conv"], "conv_w": taps[which](params["conv"]["conv_w"])}}
        else:
            raise ValueError(which)
        yield
    finally:
        lfm2._rms_norm, moe.route_sigmoid, lfm2.conv_window_taps, lfm2.conv_window_after = real["norm"], real["route"], real["taps"], real["after"]
        model.config, llm.params = c, params
        for name in ("_in_proj", "_head_norm_rope", "_dense_ffn"):
            model.__dict__.pop(name, None)


def with_drawn_norm_scales(params, seed: int, spread: float = NORM_SCALE_SPREAD):
    """``params`` with every norm scale (the leaves named ``*_norm``)
    multiplied by 1 + N(0, spread) from ``seed``: at their initial value 1 a
    head norm before rotary and one after it are the same function."""
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(jax.random.PRNGKey(seed % (2**31)), 16))

    def drawn(tree):
        out = {}
        for name, leaf in tree.items():
            if isinstance(leaf, dict):
                out[name] = drawn(leaf)
            elif name.endswith("_norm"):
                out[name] = (leaf * (1.0 + spread * jax.random.normal(next(keys), leaf.shape, jnp.float32))).astype(leaf.dtype)
            else:
                out[name] = leaf
        return out

    return drawn(params)


def _reference_check_in_worker(cfg: Mapping, seed: int, *, control: bool = False) -> dict:
    """``control``: the program runs ``fp8_weights`` of its weights while the
    reference reads the weights themselves, and no departure is tried: the
    reading that must come out not ok (``tests/test_lfm2.py`` on the CPU; on
    the chip ``refcheck_fp8_*``, PERF.md section 6)."""
    import numpy as np

    from ray_tpu.serve.llm import ShardedLLM

    eng = cfg["engine"]
    lcfg = dataclasses.replace(conv_config(cfg), n_layers=int(cfg["reference_layers"]))
    llm = ShardedLLM(lcfg, tp=int(cfg["layout"]["tp"]), seed=seed % (2**31))
    llm.params = llm.place(with_drawn_norm_scales(llm.params, seed))
    chunk = int(eng["prefill_chunk"])
    plen = chunk + chunk // 3 + 5  # two chunks, the second partly padded
    prompt = np.random.default_rng(seed).integers(1, lcfg.vocab_size, plen).astype(np.int32)
    if control:
        own = llm.params
        llm.params = llm.place(fp8_weights(own))
        return compare(llm, prompt, page=int(eng["page_size"]), chunk=chunk, ref_params=own)
    # what the tolerances must refuse is tried in every traced run: each departure has to come out not ok
    out = compare(llm, prompt, page=int(eng["page_size"]), chunk=chunk, departures=tuple(DEPARTURES))
    out["as_published_ok"] = out["ok"]
    out["departures_passed"] = [which for which in DEPARTURES if out[which]["ok"]]
    out["ok"] = bool(out["ok"] and not out["departures_passed"])
    return out


def reference_check(cfg: Mapping, seed: int, chips: int) -> dict:
    """Traced runs only, before ``serve.run``, as ``drivers/serve.py`` does
    it: a TPU actor builds the program at the configuration's widths (every
    expert, the whole vocabulary) and ``reference_layers`` layers (both dense
    ones, an attending layer and a conv layer with experts), and is killed
    afterwards."""
    import ray_tpu

    @ray_tpu.remote(num_tpus=chips)
    class RefCheck:
        def run(self, cfg, seed):
            return _reference_check_in_worker(cfg, seed)

    actor = RefCheck.remote()
    try:
        return ray_tpu.get(actor.run.remote(dict(cfg), seed), timeout=1500)
    finally:
        ray_tpu.kill(actor)


@contextlib.contextmanager
def _as_the_latent_kind():
    """``serve_mla_moe`` with this kind's configuration builder and reference
    check in place of its own, for the length of the block.  This holds only
    while ``serve_mla_moe.run`` and ``serve_mla_moe._as_the_expert_kind`` look
    both names up as globals of their module when they execute.  What fails
    otherwise: ``benchmarks/tests/test_lfm2_cell.py``'s traced rehearsal, whose
    line must carry ``reference_check["window_shape"]`` and
    ``state_bytes_per_slot``."""
    saved = (serve_mla_moe.mla_config, serve_mla_moe.reference_check)
    serve_mla_moe.mla_config, serve_mla_moe.reference_check = conv_config, reference_check
    try:
        yield
    finally:
        serve_mla_moe.mla_config, serve_mla_moe.reference_check = saved


def run(ctx) -> dict:
    conv_config(ctx.config)  # a program without the model fails here, before anything is started
    with _as_the_latent_kind():
        raw = serve_mla_moe.run(ctx)
    # ``serve_mla_moe.run`` took the live positions and the decode steps between
    # the replies nearest the capture's ends; what the pool keeps a slot, and
    # the chunks that began a sequence in the window, come from the same log
    seconds, start = float(ctx.seconds), raw["window_epoch"]
    log = [e for e in serve_moe._Client.stats_log if "state_bytes" in e[1]]
    ends = [min(log, key=lambda e: abs(e[0] - at))[1] for at in (start, start + seconds)] if log else []
    if len(ends) == 2:
        raw["counters"]["state_resets"] = ends[1]["state_resets"] - ends[0]["state_resets"]
        raw["counters"].update({key: ends[1][key] for key in ("state_bytes", "state_bytes_per_slot")})
    return raw
