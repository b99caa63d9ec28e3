"""A serving cell of a hybrid model (``kind: serve_qwen3_next``: Qwen3-Next
keys): Gated DeltaNet layers with per-slot recurrent state beside the paged
cache of the gated-attention layers, and a share of the routed experts.  The
same ``serve.run(engine_llm_deployment(...))`` replica, window and judgement
as ``drivers/serve.py``, through ``drivers/serve_moe.py``'s ``run``, whose
client and routing counters this kind shares.

How it is put in without editing either file: ``serve_moe.run`` looks up
``moe_config`` and ``reference_check`` as globals of its module when it
executes and hands them on to ``serve.py`` (``serve_moe.substituted``), so
``run`` below binds this file's two for the length of the call.  The
configuration is built FIRST: a program without the model (this PR's parent)
raises ``ImportError`` there, before a replica or a TPU worker exists.

The comparison with ``reference/qwen3_next_ref.py`` (traced runs only) and
its tolerances are below.
"""

from __future__ import annotations

import contextlib
from typing import Mapping

from benchmarks.drivers import serve_moe
from benchmarks.drivers.serve import LOGIT_TOL
from benchmarks.drivers.serve_moe import ROUTER_TOL


def hybrid_config(cfg: Mapping):
    """The program's ``Qwen3NextConfig`` for a configuration file with the
    published qwen3_next keys.  ``num_experts`` is what is HELD
    (``experts_held_from`` ..), ``num_experts_published`` the router's width."""
    import jax.numpy as jnp

    from ray_tpu.models.qwen3_next import Qwen3NextConfig

    if cfg.get("use_sliding_window") or cfg.get("tie_word_embeddings") or cfg.get("mlp_only_layers") or cfg.get("rope_scaling"):
        raise ValueError("the program's block has no sliding window, no tied head, no dense-MLP layers and no rope scaling")
    if cfg["decoder_sparse_step"] != 1 or cfg["hidden_act"] != "silu":
        raise ValueError("every layer of the program ends in experts, and they are SwiGLU")
    dtype = jnp.dtype(cfg["torch_dtype"])
    return Qwen3NextConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        hidden_dim=cfg["moe_intermediate_size"], shared_hidden_dim=cfg["shared_expert_intermediate_size"],
        n_experts=cfg["num_experts"], n_routed_experts=cfg["num_experts_published"], expert_offset=cfg["experts_held_from"],
        n_experts_per_tok=cfg["num_experts_per_tok"], norm_topk_prob=bool(cfg["norm_topk_prob"]),
        full_attention_interval=cfg["full_attention_interval"], partial_rotary_factor=cfg["partial_rotary_factor"],
        lin_key_heads=cfg["linear_num_key_heads"], lin_value_heads=cfg["linear_num_value_heads"],
        lin_key_dim=cfg["linear_key_head_dim"], lin_value_dim=cfg["linear_value_head_dim"], conv_kernel=cfg["linear_conv_kernel_dim"],
        max_seq_len=cfg["engine"]["max_seq_len"], rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        compute_dtype=dtype, param_dtype=dtype,
    )


# ---- the comparison with the reference
#
# One prompt that spans three engine chunks with a ragged tail, then
# DECODE_STEPS decode steps, through the replica's own two programs
# (``llm.engine_programs``: pool donated, one compile each) on a two-slot pool
# whose slot 1 owns the pool's first pages in reverse order and was USED
# before (a short other prompt ran through it, so its state and window are not
# zero when the prompt's first chunk arrives and must be reset by it).  Against
# the reference's one full forward over prompt + generated tokens, GIVEN THE
# PROGRAM'S ROUTING (``drivers/serve_moe.py``, for its reason: a top-k is
# discrete, and at 10 of 512 over half the rows have a near tie).
#
# Every limit lies between two readings on the chip at the published widths
# (my chip runs, PR 33, ``chiprun_out/pr33/b_refcheck*.out``; PERF.md section
# 6): what the bf16 program gives over its seeds, and what it gives with its
# weights rounded to fp8 (the nearest precision below the configuration's),
# which must come out not ok.  The comparison is after FOUR layers (one whole
# period) and ~610 tokens, where the dense and OLMoE checks stand after two:
# the residual stream's bf16 noise grows by about half a percent a layer
# (state error by layer 0.37%, 0.96%, 1.6%), so the K/V limits are this file's
# own and not ``drivers/serve.py``'s.
#
# | what                                   | bf16 program  | fp8 weights | limit |
# | K/V of the full layer, RMS             | 1.46-1.50%    | 34-35%      | KV_REL_TOL 5% |
# | K/V, worst element                     | 7.0-8.0%      | 156-181%    | KV_MAX_TOL 30% |
# | state S of a linear layer, RMS (worst) | 1.58-1.66%    | 38-40%      | STATE_REL_TOL 5% |
# | state S, worst element                 | 21-33%        | 621-655%    | STATE_MAX_TOL 100% |
# | conv window, RMS (worst layer)         | 0.98-1.04%    | 24%         | WINDOW_REL_TOL 4% |
# | greedy token's logit under the best    | 0.0           | 0.56-0.81   | LOGIT_TOL 0.08 (``serve.py``'s: 9% of the logits' deviation of 0.9) |
# | widest routing flip (relative margin)  | 0.041-0.049   | 0.24-0.28   | MARGIN 0.12 |
#
# (RMS and worst element are over the RMS of the reference's tensor.)  Those
# catch a wrong recurrence, a state not reset, not carried, or moved by a
# padded row, a missing gate, norm or renormalisation.  They cannot see the
# PRECISION of the state or of the router, because the bf16 projections
# already put the error at 1-2%; two checks isolate those, each with the
# departure it must refuse tried in every traced run:
#
# - the recurrence ALONE (RULE_TOL, RULE_MAX_TOL): the program's two functions
#   (``gated_delta_chunked`` over the same chunks with the same padded tail,
#   then ``gated_delta_step``, the state carried from call to call in float32
#   as the pool carries it) on the REFERENCE'S OWN float32 q, k, v, g, beta of
#   the first layer, against the reference's token-by-token scan, over every
#   output and the final state.  float32 arithmetic in another order reads
#   RMS 7e-5..1.3e-4 / worst element 1.7e-3..3.1e-3 on the chip (eight seeds);
#   a state rounded to bf16 between calls reads 4.4e-3 / 0.15..0.22,
#   contractions at the TPU's default precision (bf16 products) 3e-3 / 4e-2
#   (``chiprun_out/pr33/b_prec2.out``).  The pool's state must also BE float32
#   (``state_dtype``);
# - the router ALONE (``serve_moe``'s ROUTER_TOL 1e-4): the program's router on
#   the reference's router inputs returns the reference's probabilities: 0.0
#   read; a bf16 softmax 1.4-1.5e-2, and ~100 flipped choices.
#
# Also: the idle slot's state and window are still zero
# (``idle_slot_touched``); the copies of the programs that return the routing
# are held to the engine programs' tokens, pool and counter by
# ``serve_moe.judge_copies`` (inside this file's tolerances of them, the
# counter's total exact); the held share of the assignments is reported.
DECODE_STEPS = 8
KV_REL_TOL = 0.05
KV_MAX_TOL = 0.3
STATE_REL_TOL = 0.05
STATE_MAX_TOL = 1.0
WINDOW_REL_TOL = 0.04
MARGIN = 0.12
RULE_TOL = 5e-4
RULE_MAX_TOL = 0.015
SLOTS, SLOT = 2, 1


def pool_pages(plen: int, page: int) -> int:
    """Pages of ``run_paged``'s pool: the prompt, the decoded tokens and a spare page a slot."""
    return SLOTS * ((plen + DECODE_STEPS + page) // page + 1)


def tenant_tokens(chunk: int, vocab: int):
    """The short other prompt that used the slot before the prompt."""
    import numpy as np

    return ((np.arange(chunk // 2) * 7 + 3) % vocab).astype(np.int32)


def run_paged(programs, params, prompt, *, page: int, chunk: int, vocab: int):
    """``prompt`` through ``prefill`` (chunks of ``chunk``, the last padded)
    and DECODE_STEPS ``decode`` steps on slot 1 of a two-slot pool, after a
    short other prompt through the same slot.  Returns (tokens: the first and
    every decoded one, pool, slot 1's page table, routing [L, prompt +
    DECODE_STEPS, K], the earlier prompt's routing); both routings None if
    the programs return none."""
    import numpy as np

    plen = len(prompt)
    per_slot = pool_pages(plen, page) // SLOTS
    tables = np.full((SLOTS, per_slot), -1, np.int32)
    tables[SLOT] = np.arange(per_slot, dtype=np.int32)[::-1]
    row = np.ascontiguousarray(tables[SLOT])

    def prefill(pool, tokens):
        first, chosen = None, []
        for start in range(0, len(tokens), chunk):
            toks = np.zeros(chunk, np.int32)
            n_valid = min(chunk, len(tokens) - start)
            toks[:n_valid] = tokens[start : start + n_valid]
            first, pool, *rest = programs["prefill"](params, pool, row, toks, np.int32(start), np.int32(n_valid), np.int32(SLOT))
            chosen += [np.asarray(c)[:, :n_valid] for c in rest]
        return int(first), pool, chosen

    _, pool, tenant = prefill(programs["init"](), tenant_tokens(chunk, vocab))  # the slot's earlier tenant
    first, pool, routing = prefill(pool, prompt)
    tokens = [first]
    for step in range(DECODE_STEPS):
        fed, positions, active = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32), np.zeros(SLOTS, bool)
        fed[SLOT], positions[SLOT], active[SLOT] = tokens[-1], plen + step, True
        nxt, pool, *chosen = programs["decode"](params, pool, tables, fed, positions, active)
        tokens.append(int(np.asarray(nxt)[SLOT]))
        routing += [np.asarray(c)[:, SLOT : SLOT + 1] for c in chosen]
    cat = lambda parts: np.concatenate(parts, axis=1) if parts else None  # noqa: E731
    return tokens, pool, row, cat(routing), cat(tenant)


def routing_programs(llm, num_pages: int, page: int):
    """``serve_moe.routing_programs`` (copies of the two paged methods that
    also return every layer's chosen experts, recorded in front of
    ``model._ffn``) over a pool that is told its slots."""
    return {**serve_moe.routing_programs(llm, num_pages, page), "init": lambda: llm.model.init_pages(num_pages, page, SLOTS)}


def rule_alone(q, k, v, g, beta, *, chunk: int, prefill_len: int):
    """The program's recurrence on given float32 inputs [T, ...]: chunks of
    ``chunk`` over the first ``prefill_len`` tokens (the last padded, its
    padded rows with g = beta = 0 as the mixer makes them), then one token a
    call, the state carried in float32 -> (o [T, Hv, Dv], state)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import qwen3_next

    T = q.shape[0]
    state = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), jnp.float32)
    chunked, one = jax.jit(lambda *a: qwen3_next.gated_delta_chunked(*a)), jax.jit(lambda *a: qwen3_next.gated_delta_step(*a))
    outs = []
    for start in range(0, prefill_len, chunk):
        n = min(chunk, prefill_len - start)
        pad = lambda a: jnp.zeros((chunk, *a.shape[1:]), a.dtype).at[:n].set(a[start : start + n])  # noqa: E731
        o, state = chunked(pad(q), pad(k), pad(v), pad(g), pad(beta), state)
        outs.append(o[:n])
    for t in range(prefill_len, T):
        o, state = one(q[t], k[t], v[t], g[t], beta[t], state)
        outs.append(o[None])
    return jnp.concatenate(outs), state


def reference_kwargs(c) -> dict:
    """What ``qwen3_next_ref.forward`` is told of a ``Qwen3NextConfig``."""
    return dict(
        n_heads=c.n_heads, n_kv_heads=c.n_kv_heads, head_dim=c.head_dim, partial_rotary_factor=c.partial_rotary_factor, rope_theta=c.rope_theta,
        eps=c.norm_eps, full_attention_interval=c.full_attention_interval, top_k=c.n_experts_per_tok, norm_topk_prob=c.norm_topk_prob,
        expert_offset=c.expert_offset, lin_key_heads=c.lin_key_heads, lin_value_heads=c.lin_value_heads, lin_key_dim=c.lin_key_dim, lin_value_dim=c.lin_value_dim,
    )


def compare(llm, prompt, *, page: int, chunk: int, ref_params=None, departures=(),
            kv_tol=KV_REL_TOL, kv_max_tol=KV_MAX_TOL, logit_tol=LOGIT_TOL, state_tol=STATE_REL_TOL, state_max_tol=STATE_MAX_TOL,
            window_tol=WINDOW_REL_TOL, rule_tol=RULE_TOL, rule_max_tol=RULE_MAX_TOL, margin=MARGIN, router_tol=ROUTER_TOL) -> dict:
    """The program (``llm``: a ``ShardedLLM`` of a ``Qwen3NextConfig``)
    against ``qwen3_next_ref`` on one prompt.  The reference reads
    ``ref_params`` (default: the program's own weights).  Each of
    ``departures`` (``departure``'s names) is tried on the two checks that
    can see it, the recurrence alone and the router alone, and reported
    under its name with the ``ok`` those two give."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import qwen3_next_ref as ref_mod
    from ray_tpu.parallel import moe

    c = llm.cfg
    top_k, plen = c.n_experts_per_tok, len(prompt)
    num_pages = pool_pages(plen, page)
    kw = dict(page=page, chunk=chunk, vocab=c.vocab_size)
    tokens, pool, table, _, _ = run_paged(llm.engine_programs(num_pages=num_pages, page_size=page, num_slots=SLOTS), llm.params, prompt, **kw)
    copy_tokens, copy_pool, _, routing, tenant = run_paged(routing_programs(llm, num_pages, page), llm.params, prompt, **kw)
    copy_differs = copy_tokens != tokens or not all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(pool, copy_pool))
    full = jnp.asarray(np.concatenate([prompt, tokens[:-1]]).astype(np.int32))  # every token that was fed
    rows = plen + DECODE_STEPS

    params32 = ref_params if ref_params is not None else llm.params
    ref = jax.jit(lambda p, t, r: ref_mod.forward(p, t, routing=r, **reference_kwargs(c)))(params32, full, jnp.asarray(routing))

    probs = np.asarray(ref.router_probs)
    order, rel_margin = serve_moe.rank_router(probs, top_k)  # rel_margin [L, rows]
    clear = rel_margin > margin
    agree = (np.sort(routing, -1) == np.sort(order[..., :top_k], -1)).all(-1)

    def rel(got, want):
        """(RMS, largest) error over the RMS of the reference."""
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        scale = np.sqrt((want**2).mean())
        return float(np.sqrt(((got - want) ** 2).mean()) / scale), float(np.abs(got - want).max() / scale)

    pos = np.arange(rows)
    where = (slice(None), table[pos // page], pos % page)  # of a K/V member [L_full, pages, page, ...]: what the rows wrote, [L_full, rows, ...]
    written = lambda member: np.asarray(member.astype(jnp.float32))[where]  # noqa: E731
    got_k, got_v = written(pool[0]), written(pool[1])
    (k_rms, k_max), (v_rms, v_max) = rel(got_k, ref.keys), rel(got_v, ref.values)
    state, window = np.asarray(pool[3]), np.asarray(pool[4].astype(jnp.float32))
    per_layer = [rel(state[i, SLOT], ref.states[i]) for i in range(state.shape[0])]
    s_rms, s_max = max(e[0] for e in per_layer), max(e[1] for e in per_layer)
    w_rms = max(rel(window[i, SLOT], ref.windows[i])[0] for i in range(window.shape[0]))
    idle_touched = bool(np.any(state[:, 1 - SLOT]) or np.any(window[:, 1 - SLOT]))

    logits = np.asarray(ref.logits, np.float32)[:, : c.vocab_size]
    gaps = [float(logits[plen - 1 + j].max() - logits[plen - 1 + j, tok]) for j, tok in enumerate(tokens)]

    # the recurrence alone, on the reference's own inputs of the first layer
    p32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), {"emb": params32["tok_emb"], "norm": params32["moe"]["attn_norm"][0],
                                                               "lp": jax.tree.map(lambda a: a[0], params32["linear"])})
    with jax.default_matmul_precision("highest"):
        h0 = ref_mod._norm(p32["emb"][full], p32["norm"], c.norm_eps)
        q, k, v, g, beta, _, _ = ref_mod.delta_inputs(h0, p32["lp"], key_heads=c.lin_key_heads, value_heads=c.lin_value_heads,
                                                      key_dim=c.lin_key_dim, value_dim=c.lin_value_dim)
        o_ref, s_ref = jax.jit(ref_mod.delta_rule)(q, k, v, g, beta)

    def alone():
        """The recurrence and the router, each alone on the reference's
        inputs, as the program's modules have them when this is called."""
        o_got, s_got = rule_alone(q, k, v, g, beta, chunk=chunk, prefill_len=plen)
        router_err, router_flips = 0.0, 0
        for li in range(c.n_layers):
            h = jnp.asarray(ref.router_in[li]).astype(c.compute_dtype)
            wr = llm.params["moe"]["router"][li]
            w_prog, c_prog = (np.asarray(a) for a in jax.jit(lambda h, w: moe.route(h, w, top_k))(h, wr))
            p_ref, c_ref = (np.asarray(a) for a in ref_mod.route(h.astype(jnp.float32), wr, top_k))
            router_err = max(router_err, float(np.abs(w_prog / np.take_along_axis(p_ref, c_prog, -1) - 1.0).max()))
            r = -np.sort(-p_ref, -1)
            sure = (r[:, top_k - 1] - r[:, top_k]) / r[:, top_k - 1] > 10 * router_tol
            router_flips += int(((np.sort(c_prog, -1) != np.sort(c_ref, -1)).any(-1) & sure).sum())
        (o_rms, o_max), (st_rms, st_max) = rel(o_got, o_ref), rel(s_got, s_ref)
        found = {"rule_alone_err": max(o_rms, st_rms), "rule_alone_max_err": max(o_max, st_max), "router_weight_err": router_err, "router_flips": router_flips}
        ok = found["rule_alone_err"] <= rule_tol and found["rule_alone_max_err"] <= rule_max_tol and router_err <= router_tol and router_flips == 0
        return {**found, "ok": bool(ok)}

    found = alone()
    narrowed = {}
    for which in departures:
        with departure(which):
            narrowed[which] = alone()

    load = np.asarray(pool[2]).astype(np.int64)
    counted = np.bincount(np.concatenate([routing.reshape(-1), tenant.reshape(-1)]), minlength=c.n_routed_experts)  # the slot's earlier tenant too
    # the copies against the engine's programs (``serve_moe.judge_copies``): the
    # tenant's rows went through the same counter, so the reference scores them too
    tenant_probs = jax.jit(lambda p, t, r: ref_mod.forward(p, t, routing=r, **reference_kwargs(c)).router_probs)(
        params32, jnp.asarray(tenant_tokens(chunk, c.vocab_size)), jnp.asarray(tenant))
    copy_state, copy_window = np.asarray(copy_pool[3]), np.asarray(copy_pool[4].astype(jnp.float32))
    copies = serve_moe.judge_copies(
        probs, routing, load, top_k=top_k, margin=margin, expected_total=(rows + tenant.shape[1]) * c.n_layers * top_k,
        tokens=tokens, copy_tokens=copy_tokens, token_rows=range(plen - 1, rows), aside=[(np.asarray(tenant_probs), tenant)],
        pieces=[("keys", got_k, written(copy_pool[0]), kv_tol, kv_max_tol), ("values", got_v, written(copy_pool[1]), kv_tol, kv_max_tol),
                ("state", state[:, SLOT], copy_state[:, SLOT], state_tol, state_max_tol),
                ("window", window[:, SLOT], copy_window[:, SLOT], window_tol, float("inf"))],
        rest_equal=serve_moe.equal_outside(pool[0], copy_pool[0], where) and serve_moe.equal_outside(pool[1], copy_pool[1], where)
        and np.array_equal(state[:, 1 - SLOT], copy_state[:, 1 - SLOT]) and np.array_equal(window[:, 1 - SLOT], copy_window[:, 1 - SLOT]),
    )
    held = int(load[c.expert_offset : c.expert_offset + c.n_experts].sum())
    out = {
        "layers": c.n_layers, "layer_kinds": "".join(kind[0] for kind in c.layer_kinds), "prompt_len": int(plen), "decode_steps": DECODE_STEPS,
        "chunks": -(-plen // chunk), "experts_held": c.n_experts, "experts_routed": c.n_routed_experts, "top_k": top_k,
        "k_rel_err": k_rms, "v_rel_err": v_rms, "k_max_err": k_max, "v_max_err": v_max,
        "state_rel_err": s_rms, "state_max_err": s_max, "state_rel_err_by_layer": [e[0] for e in per_layer],
        "window_rel_err": w_rms, "state_dtype": str(pool[3].dtype), "idle_slot_touched": idle_touched,
        "rule_alone_err": found["rule_alone_err"], "rule_alone_max_err": found["rule_alone_max_err"],
        "logit_gap_max": max(gaps), "logit_std": float(logits[rows - 1].std()),
        "routing_agreement": float(agree.mean()), "routing_flips_above_margin": int((clear & ~agree).sum()),
        "near_tie_share": float(1.0 - clear.mean()), "flipped_margin_max": float(rel_margin[~agree].max()) if (~agree).any() else 0.0,
        "router_weight_err": found["router_weight_err"], "router_flips": found["router_flips"],
        "routing_copy_differs": bool(copy_differs), "routing_copy_flips": copies["flips"], "routing_copy_problems": copies["problems"],
        "routing_copy_flip_margin": copies["flip_margin"],
        "moe_load_total": int(load.sum()), "moe_held_share": held / max(1, int(load.sum())),
        "moe_load_miscount": int(np.abs(load - counted).sum()),
        "kv_tol": kv_tol, "kv_max_tol": kv_max_tol, "logit_tol": logit_tol, "state_tol": state_tol, "state_max_tol": state_max_tol,
        "window_tol": window_tol, "rule_tol": rule_tol, "rule_max_tol": rule_max_tol, "margin": margin, "router_tol": router_tol,
        "platform": jax.devices()[0].platform, **narrowed,
    }
    out["ok"] = bool(
        k_rms <= kv_tol and v_rms <= kv_tol and k_max <= kv_max_tol and v_max <= kv_max_tol
        and s_rms <= state_tol and s_max <= state_max_tol and w_rms <= window_tol and not idle_touched
        and out["state_dtype"] == "float32" and found["ok"]
        and max(gaps) <= logit_tol and out["routing_flips_above_margin"] == 0 and copies["ok"]
    )
    return out


def _bf16_route(h, router_w, top_k):
    """A router whose logits and softmax are bf16: what ROUTER_TOL must refuse."""
    import jax
    import jax.numpy as jnp

    probs = jax.nn.softmax(h.astype(jnp.bfloat16) @ router_w.astype(jnp.bfloat16), axis=-1)
    weights, chosen = jax.lax.top_k(probs, top_k)
    return weights.astype(jnp.float32), chosen


@contextlib.contextmanager
def departure(which: str):
    """The program with one thing narrowed, for the length of the block:
    ``bf16_state`` (the recurrent state rounded to bf16 wherever a call hands
    it on, as a bf16 pool would) or ``bf16_router`` (the router's logits and
    softmax in bf16).  Both stand in front of functions the program looks up
    in their modules when it is traced; programs built inside the block have
    them, programs built before do not."""
    import jax

    from ray_tpu.models import qwen3_next
    from ray_tpu.parallel import moe

    def to_bf16(x):
        # reduce_precision, not a cast there and back: XLA may drop a pair of converts (excess precision), as the TPU's compiler does
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def rounded(fn):
        def narrowed(*args, **kwargs):
            out, state = fn(*args[:-1], to_bf16(args[-1]), **kwargs)
            return out, to_bf16(state)

        return narrowed

    if which == "bf16_state":
        swaps = [(qwen3_next, "gated_delta_chunked", rounded(qwen3_next.gated_delta_chunked)), (qwen3_next, "gated_delta_step", rounded(qwen3_next.gated_delta_step))]
    elif which == "bf16_router":
        swaps = [(moe, "route", _bf16_route)]
    else:
        raise ValueError(which)
    saved = [(mod, name, getattr(mod, name)) for mod, name, _ in swaps]
    for mod, name, fn in swaps:
        setattr(mod, name, fn)
    try:
        yield
    finally:
        for mod, name, fn in saved:
            setattr(mod, name, fn)


def _reference_check_in_worker(cfg: Mapping, seed: int) -> dict:
    import dataclasses

    import numpy as np

    from ray_tpu.serve.llm import ShardedLLM

    eng = cfg["engine"]
    lcfg = dataclasses.replace(hybrid_config(cfg), n_layers=int(cfg["reference_layers"]))
    llm = ShardedLLM(lcfg, tp=int(cfg["layout"]["tp"]), seed=seed % (2**31))
    chunk = int(eng["prefill_chunk"])
    plen = 2 * chunk + chunk // 3 + 5  # three chunks, the third partly padded and not a multiple of the scan's block
    prompt = np.random.default_rng(seed).integers(1, lcfg.vocab_size, plen).astype(np.int32)
    # what the tolerances must refuse is tried in every traced run: each departure has to come out not ok
    out = compare(llm, prompt, page=int(eng["page_size"]), chunk=chunk, departures=("bf16_state", "bf16_router"))
    out["as_published_ok"] = out["ok"]
    out["ok"] = bool(out["ok"] and not out["bf16_state"]["ok"] and not out["bf16_router"]["ok"])
    return out


def reference_check(cfg: Mapping, seed: int, chips: int) -> dict:
    """Traced runs only, before ``serve.run``, as ``drivers/serve.py`` does
    it: a TPU actor builds the program at the configuration's widths (its
    held experts) and ``reference_layers`` layers (one whole period), and is
    killed afterwards."""
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
    check in place of its own, for the length of the block.  This holds only
    while ``serve_moe.run`` and ``serve_moe.substituted`` look both names up as
    globals of their module when they execute.  What fails otherwise:
    ``benchmarks/tests/test_qwen3_next_cell.py``'s traced rehearsal, whose line
    must carry ``reference_check["layer_kinds"]`` and ``state_bytes``."""
    saved = (serve_moe.moe_config, serve_moe.reference_check)
    serve_moe.moe_config, serve_moe.reference_check = hybrid_config, reference_check
    try:
        yield
    finally:
        serve_moe.moe_config, serve_moe.reference_check = saved


def run(ctx) -> dict:
    hybrid_config(ctx.config)  # a program without the model fails here, before anything is started
    with _as_the_expert_kind():
        raw = serve_moe.run(ctx)
    # ``serve_moe.run`` took the per-expert counter between the window's two
    # ends; the assignments seen (all of a row's choices) and held (those that
    # fell on this replica's experts) come from the same two replies
    seconds, counters = float(ctx.seconds), raw["counters"]
    start = raw["window_epoch"]
    log = serve_moe._Client.stats_log
    ends = [min(log, key=lambda e: abs(e[0] - at))[1] for at in (start, start + seconds)] if log else []
    if len(ends) == 2 and all("moe_assignments_seen" in r for r in ends) and "moe_expert_load" in counters:
        for key in ("moe_assignments_seen", "moe_assignments_held", "state_resets"):
            counters[key] = ends[1][key] - ends[0][key]
        counters["state_bytes"] = ends[1]["state_bytes"]
    return raw
