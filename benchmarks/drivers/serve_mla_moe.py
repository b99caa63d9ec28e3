"""A serving cell of a latent-attention expert model (``kind: serve_mla_moe``:
DeepSeek-V3 keys, Moonlight-16B-A3B): a cache of ONE latent row a position,
a leading dense layer, then routed experts under a sigmoid router with a
selection bias beside shared experts.  The same
``serve.run(engine_llm_deployment(...))`` replica, window and judgement as
``drivers/serve.py``, through ``drivers/serve_moe.py``'s ``run``, whose client
and routing counters this kind shares.

How it is put in without editing either file: ``serve_moe.run`` looks up
``moe_config`` and ``reference_check`` as globals of its module when it
executes and hands them on to ``serve.py`` (``serve_moe.substituted``), so
``run`` below binds this file's two for the length of the call, as
``drivers/serve_qwen3_next.py`` does.  The configuration is built FIRST: a
program without the model (this PR's parent) raises ``ImportError`` there,
before a replica or a TPU worker exists.

The comparison with ``reference/deepseek_v3_ref.py`` (traced runs only) and
its tolerances are below.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Mapping

from benchmarks.drivers import serve_moe
from benchmarks.drivers import serve_qwen3_next as hybrid
from benchmarks.drivers.serve import LOGIT_TOL
from benchmarks.drivers.serve_moe import ROUTER_TOL
from benchmarks.drivers.serve_qwen3_next import DECODE_STEPS, SLOT, SLOTS


def mla_config(cfg: Mapping):
    """The program's ``DeepseekV3Config`` for a configuration file with the
    published deepseek_v3 keys."""
    import jax.numpy as jnp

    from ray_tpu.models.deepseek_v3 import DeepseekV3Config

    if cfg.get("q_lora_rank") or cfg.get("rope_scaling") or cfg.get("tie_word_embeddings") or cfg.get("attention_bias") or cfg.get("num_nextn_predict_layers"):
        raise ValueError("the program's block has no low-rank query, no rope scaling, no tied head, no attention bias and no next-token modules")
    if (cfg["n_group"], cfg["topk_group"], cfg["scoring_func"], cfg["topk_method"], cfg["moe_layer_freq"], cfg["hidden_act"]) != (1, 1, "sigmoid", "noaux_tc", 1, "silu"):
        raise ValueError("the program's router is a sigmoid with a selection bias over ONE group, every layer after the dense ones has experts, and they are SwiGLU")
    dtype = jnp.dtype(cfg["torch_dtype"])
    return DeepseekV3Config(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"], n_heads=cfg["num_attention_heads"],
        hidden_dim=cfg["moe_intermediate_size"], dense_hidden_dim=cfg["intermediate_size"], first_k_dense=cfg["first_k_dense_replace"],
        n_experts=cfg["n_routed_experts"], n_experts_per_tok=cfg["num_experts_per_tok"], n_shared_experts=cfg["n_shared_experts"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"], qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        routed_scaling_factor=cfg["routed_scaling_factor"], norm_topk_prob=bool(cfg["norm_topk_prob"]),
        max_seq_len=cfg["engine"]["max_seq_len"], rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"], compute_dtype=dtype, param_dtype=dtype,
    )


# ---- the comparison with the reference
#
# One prompt that spans two engine chunks with a ragged tail, then DECODE_STEPS
# decode steps, through the replica's own two programs
# (``llm.engine_programs``: pool donated, one compile each) on a two-slot pool
# whose slot 1 owns the pool's first pages in reverse order and held a short
# other prompt before (``serve_qwen3_next.run_paged``, as it stands).  Against
# the reference's one full UNABSORBED forward over prompt + generated tokens,
# GIVEN THE PROGRAM'S ROUTING (``drivers/serve_moe.py``, for its reason: a
# top-k is discrete): the pool's latent rows layer by layer, the greedy
# tokens' logits, the choices where the reference's own margin is clear, and
# the routing counter through ``serve_moe.judge_copies``, handed the SELECTION
# scores sigma + b, whose 6th and 7th a choice lies between.
#
# Those see a wrong projection, norm or rotary in what the cache keeps.  They
# cannot see what only weighs a layer's OUTPUT at these weights (at two or
# three layers of N(0, 0.02) an attention scale of sqrt(128) moves the next
# layer's rows by 1-2%, inside bf16 noise), nor the PRECISION of the router
# or of a norm (the bf16 matmuls around them already put the error at 0.5%).
# So four checks isolate a part each, the program's own function on the
# REFERENCE'S inputs, and every traced run tries on them the departures they
# must refuse (``DEPARTURES``; each has to come out not ok):
#
# - the norm alone (NORM_TOL): the model file's RMSNorm on the reference's
#   float32 residual stream;
# - the router alone (``serve_moe``'s ROUTER_TOL): ``parallel/moe.route_sigmoid``
#   with the bias, on the reference's router inputs, returns the
#   reference's scores of its chosen experts, and its choices wherever the
#   selection margin is sure;
# - the expert layer alone (FFN_TOL): the model's ``_ffn`` of the first expert
#   layer on the reference's residual stream, against the reference's routed
#   sum + shared expert following the choices that call made;
# - the mixer alone (ATTN_TOL, and the row limits): the model's ``_mla`` of
#   layer 0 through a small pool, the same chunks then one row a step, on the
#   reference's residual stream, against the reference's unabsorbed attention.
#
# Every limit lies between two readings on the chip at the published widths,
# both of the tree as committed (PERF.md section 6, PR 45): what the bf16
# program gives over its seeds (the check inside the three traced runs
# ``chiprun_out/pr45/D1_*_t1.detail.json``), and what it gives with its
# weights rounded to fp8 (``fp8_weights``: e4m3, the nearest precision below
# the configuration's bf16; ``_reference_check_in_worker(control=True)``,
# ``D1_refcheck_fp8_*``), which must come out not ok -- as it does at the
# tiny size in ``tests/test_moonlight.py``.
#
# | what                                     | bf16 program   | fp8 weights | limit |
# | latent rows, RMS (worst layer: the third)| 0.826-0.837%   | 9.7%        | ROW_REL_TOL 3% |
# | latent rows, worst element               | 4.0-4.2%       | 50%         | ROW_MAX_TOL 15% |
# | greedy token's logit under the best      | 0.0-0.0073     | 0.13        | LOGIT_TOL 0.08 (``serve.py``'s: 9% of the logits' deviation of 0.9) |
# | widest routing flip (relative margin)    | 0.0036-0.0049  | 0.054, 56 flips above the limit | MARGIN 0.015 |
# | mixer alone, its output, RMS             | 0.463-0.465%   | 4.9%        | ATTN_TOL 1.5% |
# | mixer alone, its rows, RMS / worst       | 0.29% / 2.0-2.5% | 2.7% / 11.6% | the row limits above (fp8 is inside these two: the output refuses it) |
# | expert layer alone, its output, RMS      | 0.379-0.380%   | 4.7%        | FFN_TOL 1.5% |
# | the norm alone, worst element            | 1e-6           | (a bf16 norm: 2.7-3.5%) | NORM_TOL 1e-4 |
# | the router alone, weights                | 0.0            | (a bf16 router: 3.8e-3) | ROUTER_TOL 1e-4 (``serve_moe``'s) |
#
# (RMS and worst element are over the RMS of the reference's tensor; three
# seeds in bf16, one in fp8; an earlier tree's three and one read the same,
# ``refcheck_*.json``.)  In 99.9-100% of rows the chosen set is not the raw
# scores' top 6 (``bias_decides_share``): the selection bias, drawn at the
# size of the scores' own spread, decides.  A third or more of the rows are
# near ties under MARGIN (``near_tie_share`` 0.31-0.46).
ROW_REL_TOL = 0.03
ROW_MAX_TOL = 0.15
MARGIN = 0.015
ATTN_TOL = 0.015
FFN_TOL = 0.015
NORM_TOL = 1e-4


def reference_kwargs(c) -> dict:
    """What ``deepseek_v3_ref.forward`` is told of a ``DeepseekV3Config``."""
    return dict(
        n_heads=c.n_heads, kv_lora_rank=c.kv_lora_rank, qk_nope_head_dim=c.qk_nope_head_dim, qk_rope_head_dim=c.qk_rope_head_dim, v_head_dim=c.v_head_dim,
        rope_theta=c.rope_theta, eps=c.norm_eps, top_k=c.n_experts_per_tok, norm_topk_prob=c.norm_topk_prob, routed_scaling_factor=c.routed_scaling_factor,
    )


def _rel(got, want):
    """(RMS, largest) error over the RMS of the reference."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = float(np.sqrt((want**2).mean())) or 1.0
    return float(np.sqrt(((got - want) ** 2).mean()) / scale), float(np.abs(got - want).max() / scale)


def mixer_alone(model, ap, x, *, page: int, chunk: int, prefill_len: int):
    """The model's latent mixer of ONE layer (weights ``ap``) on given
    residual rows x [S, E], through a pool of its own as the two paged
    programs drive it: the first ``prefill_len`` rows in chunks of ``chunk``
    (the last padded), the rest one row a call on slot SLOT of SLOTS.
    Returns (what the mixer adds [S, E], the pool's rows [S, latent], their padding left out)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    S = x.shape[0]
    per_slot = (S + page) // page + 1
    n_pages = SLOTS * per_slot
    tables = np.full((SLOTS, per_slot), -1, np.int32)
    tables[SLOT] = np.arange(per_slot, dtype=np.int32)[::-1]
    pool = model.init_pages(n_pages, page)[0][:1]

    def call(pool, xs, tabs, pos, valid):
        """xs [B, Q, E] at positions pos [B, Q] of the slots whose tables are tabs [B, MP]."""
        wpage = jnp.take_along_axis(tabs, pos // page, axis=1)
        wpage = jnp.where(valid & (wpage >= 0), wpage, n_pages).reshape(-1)
        blocks, n_blocks = model._walk_blocks(tabs, page, pos, valid)
        return model._mla(xs, ap, 0, pool, wpage, (pos % page).reshape(-1), blocks, pos, valid, n_blocks)

    call = jax.jit(call)
    x = jnp.asarray(x).astype(model.config.compute_dtype)
    outs = []
    for start in range(0, prefill_len, chunk):
        n = min(chunk, prefill_len - start)
        xs = jnp.zeros((1, chunk, x.shape[1]), x.dtype).at[0, :n].set(x[start : start + n])
        out, pool = call(pool, xs, tables[SLOT : SLOT + 1], (start + np.arange(chunk, dtype=np.int32))[None], (np.arange(chunk) < n)[None])
        outs.append(out[0, :n])
    for t in range(prefill_len, S):
        xs = jnp.zeros((SLOTS, 1, x.shape[1]), x.dtype).at[SLOT, 0].set(x[t])
        pos, valid = np.zeros((SLOTS, 1), np.int32), np.zeros((SLOTS, 1), bool)
        pos[SLOT], valid[SLOT] = t, True
        out, pool = call(pool, xs, tables, pos, valid)
        outs.append(out[SLOT])
    at = np.arange(S)
    return jnp.concatenate(outs).astype(jnp.float32), np.asarray(pool.astype(jnp.float32))[0, tables[SLOT][at // page], at % page, : model.config.latent_dim]


def compare(llm, prompt, *, page: int, chunk: int, ref_params=None, departures=(),
            row_tol=ROW_REL_TOL, row_max_tol=ROW_MAX_TOL, logit_tol=LOGIT_TOL, margin=MARGIN, router_tol=ROUTER_TOL,
            attn_tol=ATTN_TOL, ffn_tol=FFN_TOL, norm_tol=NORM_TOL) -> dict:
    """The program (``llm``: a ``ShardedLLM`` of a ``DeepseekV3Config``)
    against ``deepseek_v3_ref`` on one prompt.  The reference reads
    ``ref_params`` (default: the program's own weights) and ``llm.cfg``.  Each
    of ``departures`` (``DEPARTURES``' names) is planted and tried on the one
    check alone that sees it, and reported under its name with that check's
    ``ok``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import deepseek_v3_ref as ref_mod
    from ray_tpu.models import deepseek_v3
    from ray_tpu.parallel import moe

    c = llm.cfg
    top_k, plen, n_moe = c.n_experts_per_tok, len(prompt), c.n_layers - c.first_k_dense
    num_pages = hybrid.pool_pages(plen, page)
    kw = dict(page=page, chunk=chunk, vocab=c.vocab_size)
    tokens, pool, table, _, _ = hybrid.run_paged(llm.engine_programs(num_pages=num_pages, page_size=page, num_slots=SLOTS), llm.params, prompt, **kw)
    copy_tokens, copy_pool, _, routing, tenant = hybrid.run_paged(hybrid.routing_programs(llm, num_pages, page), llm.params, prompt, **kw)
    copy_differs = copy_tokens != tokens or not all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(pool, copy_pool))
    full = jnp.asarray(np.concatenate([prompt, tokens[:-1]]).astype(np.int32))  # every token that was fed
    rows = plen + DECODE_STEPS

    own = ref_params if ref_params is not None else llm.params
    pub = ref_mod.to_published_layout(own, c.first_k_dense)
    ref = ref_mod.forward(pub, full, routing=jnp.asarray(routing), **reference_kwargs(c))
    select = np.asarray(ref.select)
    order, rel_margin = serve_moe.rank_router(select, top_k)  # rel_margin [L_moe, rows]
    clear = rel_margin > margin
    agree = (np.sort(routing, -1) == np.sort(order[..., :top_k], -1)).all(-1)
    raw_order, _ = serve_moe.rank_router(np.asarray(ref.scores), top_k)
    bias_decides = (np.sort(raw_order[..., :top_k], -1) != np.sort(order[..., :top_k], -1)).any(-1)  # rows whose chosen set is not the raw scores' top-k

    pos = np.arange(rows)
    where = (slice(None), table[pos // page], pos % page)  # of the pages member [L, pages, page, row]: what the rows wrote, [L, rows, row]
    written = lambda member: np.asarray(member.astype(jnp.float32))[where]  # noqa: E731
    got_rows = written(pool[0])
    padding_written = bool(np.any(got_rows[..., c.latent_dim :]))  # a row is latent_dim values, then zeros (``cache_row_dim``)
    per_layer = [_rel(got_rows[i, :, : c.latent_dim], ref.rows[i]) for i in range(c.n_layers)]
    row_rms, row_max = max(e[0] for e in per_layer), max(e[1] for e in per_layer)
    logits = np.asarray(ref.logits, np.float32)[:, : c.vocab_size]
    gaps = [float(logits[plen - 1 + j].max() - logits[plen - 1 + j, tok]) for j, tok in enumerate(tokens)]

    # ---- a part alone, on the reference's inputs, as the program's modules and ``llm.model`` have it when called
    first_moe = c.first_k_dense
    moe32 = jax.tree.map(lambda a: jnp.asarray(a[0], jnp.float32), own["moe"])
    ffn_x = jnp.asarray(ref.ffn_in[first_moe])
    with jax.default_matmul_precision("highest"):
        router_in = [ref_mod._norm(jnp.asarray(ref.ffn_in[first_moe + i]), jnp.asarray(own["moe"]["ffn_norm"][i], jnp.float32), c.norm_eps) for i in range(n_moe)]

    def norm_alone():
        x, w = jnp.asarray(ref.attn_in[-1]), jnp.asarray(own["attn"]["attn_norm"][-1], jnp.float32)
        got = jax.jit(lambda x, w: deepseek_v3._rms_norm(x, w, c.norm_eps))(x, w)
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
        # the residual stream in float32: the layer rounds its normed input to the compute type itself, and x + y is
        # then a float32 sum, so that out - x is what the layer added and not that rounded to x's last bit
        x = ffn_x[None]
        out, chosen = jax.jit(lambda x, mp: model._ffn(x, mp))(x, mp)  # a function of its own: nothing traced before is found again

        @jax.jit
        def want(x, chosen, moe32):
            with jax.default_matmul_precision("highest"):
                h = ref_mod._norm(x, moe32["ffn_norm"], c.norm_eps)
                sigma, _, _ = ref_mod.route(h, moe32["router"], moe32["router_bias"], top_k)
                weight = ref_mod.routed_weights(sigma, chosen, norm_topk_prob=c.norm_topk_prob, routed_scaling_factor=c.routed_scaling_factor)
                return ref_mod.expert_ffn(h, weight, chosen, moe32["w_gate"], moe32["w_up"], moe32["w_down"]) + ref_mod.swiglu(h, moe32["ws_gate"], moe32["ws_up"], moe32["ws_down"])

        return {"ffn_alone_err": _rel((out - x)[0], want(x[0], chosen[0], moe32))[0]}

    def attn_alone():
        ap = jax.tree.map(lambda a: a[0], llm.params["attn"])
        out, got = mixer_alone(llm.model, ap, ref.attn_in[0], page=page, chunk=chunk, prefill_len=plen)
        (o_rms, _), (r_rms, r_max) = _rel(out, ref.attn_out[0]), _rel(got, ref.rows[0])
        return {"attn_alone_err": o_rms, "attn_alone_row_err": r_rms, "attn_alone_row_max_err": r_max}

    checks = {"norm": norm_alone, "router": router_alone, "ffn": ffn_alone, "attn": attn_alone}

    def passes(found: dict) -> bool:
        limits = {"norm_alone_err": norm_tol, "router_weight_err": router_tol, "router_flips": 0, "ffn_alone_err": ffn_tol,
                  "attn_alone_err": attn_tol, "attn_alone_row_err": row_tol, "attn_alone_row_max_err": row_max_tol}
        return all(found[k] <= limits[k] for k in found)

    found = {k: v for check in checks.values() for k, v in check().items()}
    narrowed = {}
    for which in departures:
        with departure(which, llm):
            seen = checks[DEPARTURES[which]]()
        narrowed[which] = {**seen, "ok": passes(seen)}

    load = np.asarray(pool[1]).astype(np.int64)
    counted = np.bincount(np.concatenate([routing.reshape(-1), tenant.reshape(-1)]), minlength=c.n_experts)  # the slot's earlier tenant too
    tenant_select = ref_mod.forward(pub, jnp.asarray(hybrid.tenant_tokens(chunk, c.vocab_size)), routing=jnp.asarray(tenant), **reference_kwargs(c)).select
    copies = serve_moe.judge_copies(
        select, routing, load, top_k=top_k, margin=margin, expected_total=(rows + tenant.shape[1]) * n_moe * top_k,
        tokens=tokens, copy_tokens=copy_tokens, token_rows=range(plen - 1, rows), aside=[(np.asarray(tenant_select), tenant)],
        pieces=[("rows", got_rows, written(copy_pool[0]), row_tol, row_max_tol)], rest_equal=serve_moe.equal_outside(pool[0], copy_pool[0], where),
    )
    out = {
        "layers": c.n_layers, "dense_layers": c.first_k_dense, "prompt_len": int(plen), "decode_steps": DECODE_STEPS, "chunks": -(-plen // chunk),
        "experts": c.n_experts, "top_k": top_k, "latent_dim": c.latent_dim, "row_dim": int(pool[0].shape[-1]), "padding_written": padding_written, "pool_members": len(pool),
        "row_rel_err": row_rms, "row_max_err": row_max, "row_rel_err_by_layer": [e[0] for e in per_layer],
        "logit_gap_max": max(gaps), "logit_std": float(logits[rows - 1].std()),
        "routing_agreement": float(agree.mean()), "routing_flips_above_margin": int((clear & ~agree).sum()),
        "near_tie_share": float(1.0 - clear.mean()), "flipped_margin_max": float(rel_margin[~agree].max()) if (~agree).any() else 0.0,
        "bias_decides_share": float(bias_decides.mean()), **found,
        "routing_copy_differs": bool(copy_differs), "routing_copy_flips": copies["flips"], "routing_copy_problems": copies["problems"],
        "routing_copy_flip_margin": copies["flip_margin"],
        "moe_load_total": int(load.sum()), "moe_load_miscount": int(np.abs(load - counted).sum()),
        "row_tol": row_tol, "row_max_tol": row_max_tol, "logit_tol": logit_tol, "margin": margin, "router_tol": router_tol,
        "attn_tol": attn_tol, "ffn_tol": ffn_tol, "norm_tol": norm_tol, "platform": jax.devices()[0].platform, **narrowed,
    }
    out["ok"] = bool(
        row_rms <= row_tol and row_max <= row_max_tol and max(gaps) <= logit_tol and out["routing_flips_above_margin"] == 0
        and out["row_dim"] == c.cache_row_dim and not padding_written and out["pool_members"] == 2 and passes(found) and copies["ok"]
    )
    return out


# ---- what the tolerances must refuse: a name -> the check alone that sees it
DEPARTURES = {
    "bf16_norm": "norm", "bf16_router": "router", "no_bias": "router", "bias_as_weight": "router",
    "no_scale": "ffn", "no_renorm": "ffn", "shared_dropped": "ffn", "shared_halved": "ffn",
    "no_latent_norm": "attn", "rotary_on_c": "attn", "scale_sqrt128": "attn", "scale_sqrt576": "attn", "values_over_576": "attn",
}


@contextlib.contextmanager
def departure(which: str, llm):
    """The program with one thing planted, for the length of the block: what
    is traced inside has it, what was traced before does not.  ``llm.cfg``
    (what the reference is told) stays as published; what changes is a
    function the program looks up in its module when traced (the model
    file's norm, ``moe.route_sigmoid``), a method of ``llm.model`` (an instance
    attribute in front of the class's), its configuration, or ``llm.params``.

    bf16_norm / bf16_router: float32 where the configuration says so, narrowed;
    no_bias: the top-k of the raw scores; bias_as_weight: the weights are
    scores + bias; no_scale / no_renorm: ``routed_scaling_factor`` 1 / the raw
    scores as weights; shared_dropped / shared_halved; no_latent_norm: c goes
    into the row as projected; rotary_on_c: c is rotated like k_rope;
    scale_sqrt128 / scale_sqrt576: 1/sqrt(dn) / 1/sqrt(r + dr) for 1/sqrt(dn
    + dr); values_over_576: a position's values are its whole row, the dr
    rotary columns folded onto the first dr."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import deepseek_v3
    from ray_tpu.parallel import moe

    model, c = llm.model, llm.model.config
    r, dr = c.kv_lora_rank, c.qk_rope_head_dim
    real = {"norm": deepseek_v3._rms_norm, "route": moe.route_sigmoid, "attend": model._paged_attend, "latent": model._latent}
    to_bf16 = lambda x: jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)  # noqa: E731 -- not a cast there and back, which XLA may drop

    def bf16_norm(x, scale, eps):
        x = to_bf16(x.astype(jnp.float32))
        return to_bf16(to_bf16(x * to_bf16(jax.lax.rsqrt(to_bf16((x**2).mean(-1, keepdims=True)) + eps))) * scale)

    def skip_latent_norm(x, scale, eps):
        return x.astype(jnp.float32) * scale if x.shape[-1] == r else real["norm"](x, scale, eps)

    def bf16_route(h, router_w, top_k, bias):
        scores = jax.nn.sigmoid(h.astype(jnp.bfloat16) @ router_w.astype(jnp.bfloat16))
        _, chosen = jax.lax.top_k(scores.astype(jnp.float32) + bias.astype(jnp.float32), top_k)
        return jnp.take_along_axis(scores, chosen, -1).astype(jnp.float32), chosen

    def bias_as_weight(h, router_w, top_k, bias):
        weights, chosen = real["route"](h, router_w, top_k, bias)
        return weights + bias.astype(jnp.float32)[chosen], chosen

    def rotary_on_c(x, ap, positions):
        q, row = real["latent"](x, ap, positions)
        turned = deepseek_v3._partial_rope(row[..., None, :r], positions, c.rope_theta, r)[..., 0, :]
        return q, jnp.concatenate([turned, row[..., r:]], axis=-1)

    def attend_with(**told):
        return lambda *a, **kw: real["attend"](*a, **{**kw, **told})

    def values_over_576(q, *a, **kw):
        B, Q, H, _ = q.shape
        o = real["attend"](q, *a, **{**kw, "value_dim": r + dr}).reshape(B, Q, H, r + dr)
        return o[..., :r].at[..., :dr].add(o[..., r:]).reshape(B, Q, H * r)

    modules = {"bf16_norm": (deepseek_v3, "_rms_norm", bf16_norm), "no_latent_norm": (deepseek_v3, "_rms_norm", skip_latent_norm),
               "bf16_router": (moe, "route_sigmoid", bf16_route), "bias_as_weight": (moe, "route_sigmoid", bias_as_weight),
               "no_bias": (moe, "route_sigmoid", lambda h, w, k, bias: real["route"](h, w, k))}
    methods = {"rotary_on_c": ("_latent", rotary_on_c), "scale_sqrt128": ("_paged_attend", attend_with(scale=c.qk_nope_head_dim**-0.5)),
               "scale_sqrt576": ("_paged_attend", attend_with(scale=(r + dr) ** -0.5)), "values_over_576": ("_paged_attend", values_over_576)}
    configs = {"no_scale": dict(routed_scaling_factor=1.0), "no_renorm": dict(norm_topk_prob=False)}
    shared = {"shared_dropped": 0.0, "shared_halved": 0.5}
    params = llm.params
    try:
        if which in modules:
            mod, name, fn = modules[which]
            setattr(mod, name, fn)
        elif which in methods:
            setattr(model, *methods[which])
        elif which in configs:
            model.config = dataclasses.replace(c, **configs[which])
        elif which in shared:
            llm.params = {**params, "moe": {**params["moe"], "ws_down": params["moe"]["ws_down"] * shared[which]}}
        else:
            raise ValueError(which)
        yield
    finally:
        deepseek_v3._rms_norm, moe.route_sigmoid, model.config, llm.params = real["norm"], real["route"], c, params
        for name in ("_latent", "_paged_attend"):
            model.__dict__.pop(name, None)


def fp8_weights(params):
    """The control the limits' second reading is taken with: every matrix
    rounded to fp8 (e4m3, scaled to its largest element), the nearest
    precision below the configuration's bf16; norms and biases as they are."""
    import jax
    import jax.numpy as jnp

    def rounded(w):
        if w.ndim < 2:
            return w
        scale = jnp.max(jnp.abs(w.astype(jnp.float32))) / 448.0
        return ((w.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale).astype(w.dtype)

    return jax.tree.map(rounded, params)


def _reference_check_in_worker(cfg: Mapping, seed: int, *, control: bool = False) -> dict:
    """``control``: the program runs ``fp8_weights`` of its weights while the
    reference reads the weights themselves, and no departure is tried: the
    reading that must come out not ok (``tests/test_moonlight.py`` on the
    CPU; on the chip ``refcheck_fp8_*``, PERF.md section 6)."""
    import numpy as np

    from ray_tpu.serve.llm import ShardedLLM

    eng = cfg["engine"]
    lcfg = dataclasses.replace(mla_config(cfg), n_layers=int(cfg["reference_layers"]))
    llm = ShardedLLM(lcfg, tp=int(cfg["layout"]["tp"]), seed=seed % (2**31))
    gain = float(cfg.get("reference_attention_gain", 1.0))
    if gain != 1.0:
        # the CPU rehearsal's widths: N(0, 0.02) at 64 wide leaves every score near zero and every softmax flat, so that
        # no attention scale could show; its ``tiny`` block says by how much the mixers' matrices are multiplied here
        attn = {k: v * gain if v.ndim > 2 else v for k, v in llm.params["attn"].items()}
        llm = ShardedLLM(lcfg, tp=int(cfg["layout"]["tp"]), init={**llm.params, "attn": attn})
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
    expert, the whole vocabulary) and ``reference_layers`` layers (the dense
    one and two of experts), and is killed afterwards."""
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
def _as_the_expert_kind():
    """``serve_moe`` with this kind's configuration builder and reference
    check in place of its own, for the length of the block
    (``serve_qwen3_next._as_the_expert_kind``, for its reason).  What fails
    otherwise: ``benchmarks/tests/test_moonlight_cell.py``'s traced rehearsal,
    whose line must carry ``reference_check["row_dim"]`` and
    ``cache_bytes_per_position``."""
    saved = (serve_moe.moe_config, serve_moe.reference_check)
    serve_moe.moe_config, serve_moe.reference_check = mla_config, reference_check
    try:
        yield
    finally:
        serve_moe.moe_config, serve_moe.reference_check = saved


def run(ctx) -> dict:
    mla_config(ctx.config)  # a program without the model fails here, before anything is started
    with _as_the_expert_kind():
        raw = serve_moe.run(ctx)
    # what the latent cache keeps a position, and the positions the decode steps
    # had live: between the replies nearest the ends of the profiler's capture
    # in a traced run (``serve_moe.run``, for its reason: after the capture the
    # stop's stall drains the slots, and the steps the trace timed are the
    # capture's), else between those at the window's two ends
    seconds, start = float(ctx.seconds), raw["window_epoch"]
    lo = start + seconds / 3.0
    span = (lo, lo + float(ctx.traffic.get("trace_seconds", 3.0))) if ctx.trace else (start, start + seconds)
    log = [e for e in serve_moe._Client.stats_log if "ctx_positions_live" in e[1]]
    ends = [min(log, key=lambda e: abs(e[0] - at))[1] for at in span] if log else []
    if len(ends) == 2 and ends[1]["decode_steps"] > ends[0]["decode_steps"]:
        for key in ("ctx_positions_live", "decode_steps"):
            raw["counters"][key] = ends[1][key] - ends[0][key]
        raw["counters"]["cache_bytes_per_position"] = ends[1]["cache_bytes_per_position"]
    return raw
