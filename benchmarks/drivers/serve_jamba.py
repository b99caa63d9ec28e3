"""A serving cell of a dense state-space hybrid (``kind: serve_jamba``: Jamba
keys): Mamba-1 selective-scan layers with per-slot state beside the paged
cache of the attending layers, a dense SwiGLU after every mixer, a tied head.
The same ``serve.run(engine_llm_deployment(...))`` replica, window and
judgement as ``drivers/serve.py``, whose ``run`` this driver calls.

How it is put in without editing a file: ``serve_moe.substituted()`` swaps
``serve_moe``'s ``moe_config``, ``reference_check`` and stats-keeping client
into ``drivers/serve.py``, looking the first two up as globals of ITS module
when it executes, so ``run`` below binds this file's two for the length of the
call (as ``drivers/serve_qwen3_next.py`` does).  ``serve_moe.run`` itself is
not used: it holds a traced run to routing counters, which a model without
experts has none of, so the slot samples it keeps for the readers
(``slots_decode_samples``, ``slots_active_unstalled``) are taken here from the
same client's replies.  The configuration is built FIRST: a program without
the model (this PR's parent) raises ``ImportError`` there, before a replica or
a TPU worker exists.

The comparison with ``reference/jamba_ref.py`` (traced runs only) and its
tolerances are below.
"""

from __future__ import annotations

import contextlib
from typing import Mapping

from benchmarks.drivers import serve as dense
from benchmarks.drivers import serve_moe
from benchmarks.drivers.serve import LOGIT_TOL

# the same run through the pool as the other model with per-slot state: a two-slot pool whose slot 1 was used before,
# the prompt in chunks, then DECODE_STEPS decode steps (programs that return no routing give none)
from benchmarks.drivers.serve_qwen3_next import DECODE_STEPS, SLOT, SLOTS, pool_pages, run_paged


def jamba_config(cfg: Mapping):
    """The program's ``JambaConfig`` for a configuration file with the
    published jamba keys."""
    import jax.numpy as jnp

    from ray_tpu.models.jamba import JambaConfig

    if cfg.get("sliding_window") or not cfg["tie_word_embeddings"] or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"]:
        raise ValueError("the program's block has no sliding window, a tied head, no bias on the Mamba projections and one on its conv")
    if cfg["num_experts"] != 1 or cfg["hidden_act"] != "silu":
        raise ValueError("every layer of the program ends in ONE dense SwiGLU")
    dtype = jnp.dtype(cfg["torch_dtype"])
    return JambaConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], hidden_dim=cfg["intermediate_size"],
        attn_layer_period=cfg["attn_layer_period"], attn_layer_offset=cfg["attn_layer_offset"],
        d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"], expand=cfg["mamba_expand"], dt_rank=cfg["mamba_dt_rank"],
        max_seq_len=cfg["engine"]["max_seq_len"], norm_eps=cfg["rms_norm_eps"], compute_dtype=dtype, param_dtype=dtype,
    )


# ---- the comparison with the reference
#
# One prompt that spans three engine chunks with a ragged tail, then
# DECODE_STEPS decode steps, through the replica's own two programs
# (``llm.engine_programs``: pool donated, one compile each) on a two-slot pool
# whose slot 1 owns the pool's first pages in reverse order and was USED
# before (a short other prompt ran through it, so its state and window are not
# zero when the prompt's first chunk arrives and must be reset by it).  Against
# the reference's one full forward over prompt + generated tokens, at the
# published widths over ``reference_layers`` (the configuration's file: four
# layers, Mamba Mamba attention Mamba, so the attending layer reads what two
# scans left and a scan reads what attention left).
#
# Every limit lies between two readings on the chip (my chip runs, PR 35,
# ``chiprun_out/pr35/refcheck*.out`` and the traced runs; PERF.md section 6):
# what the bf16 program gives over its seeds, and what it gives with its
# matrices rounded to fp8 (the nearest precision below the configuration's),
# which must come out not ok.
#
# | what                                   | bf16 program   | fp8 weights  | limit |
# | K/V of the attending layer, RMS        | 0.99-1.02%     | 22.5-22.7%   | KV_REL_TOL 5% |
# | K/V, worst element                     | 4.3-5.1%       | 98-116%      | KV_MAX_TOL 20% |
# | state of a Mamba layer, RMS (worst)    | 1.14-1.39%     | 24.8-28.2%   | STATE_REL_TOL 5% |
# | state, worst element                   | 29-84%         | 571-731%     | STATE_MAX_TOL 250% |
# | conv window, RMS (worst layer)         | 1.14-1.21%     | 26.4-26.7%   | WINDOW_REL_TOL 5% |
# | greedy token's logit under the best    | 0.0-0.0006     | 0.53-0.60    | LOGIT_TOL 0.08 (``serve.py``'s: 8% of the logits' deviation of 1.0) |
#
# (RMS and worst element are over the RMS of the reference's tensor; the
# state's error by layer reads 0.3-0.4%, 0.7-1.0%, 1.1-1.4%: the residual
# stream's bf16 noise grows by about half a percent a layer, as in
# ``drivers/serve_qwen3_next.py``.  The state's worst element is large
# because most of a state's 82 k elements are far under its RMS: the
# channels with a slow decay hold nearly all of it.)  Those catch a wrong
# recurrence, a state not reset, not carried, or moved by a padded row, a
# missing inner norm, conv bias or gate.  They cannot see the PRECISION of
# the state, because the bf16 projections already put the error at a percent
# (with the state rounded to bf16 between calls the same run reads 1.24-1.27%
# against 1.14-1.18%); one more check isolates it, with the departure it must
# refuse tried in every traced run:
#
# - the recurrence ALONE (RULE_TOL, RULE_MAX_TOL): the program's
#   ``ops/selective_scan.py`` (on the chip its kernel) over the same chunks
#   with the same padded tail and then one row a call, the state carried from
#   call to call in a float32 pool as the engine's pool carries it, on the
#   REFERENCE'S OWN float32 u, dt, B, C, A of the first layer, against the
#   reference's token-by-token scan, over every output and the final state.
#   The kernel reads RMS 1.03e-7..1.07e-7 / worst element 2.2e-6..4.0e-6 on
#   the chip (four seeds: float32 arithmetic in another order); a state
#   rounded to bf16 between calls 3.1e-3..3.8e-3 / 0.061..0.12.  The limits
#   are near the geometric middle of each pair.  The pool's state must also
#   BE float32 (``state_dtype``).
#
# Also: the idle slot's state and window are still zero (``idle_slot_touched``).
KV_REL_TOL = 0.05
KV_MAX_TOL = 0.2
STATE_REL_TOL = 0.05
STATE_MAX_TOL = 2.5
WINDOW_REL_TOL = 0.05
RULE_TOL = 2e-5
RULE_MAX_TOL = 5e-4

def rule_alone(u, dt, Bm, Cm, A, D, *, chunk: int, prefill_len: int):
    """The program's scan on given float32 inputs (u, dt [T, d_inner]; Bm, Cm
    [T, N]; A [N, d_inner]; D [d_inner]): chunks of ``chunk`` over the first
    ``prefill_len`` tokens (the last padded, its padded rows with dt = 0 as the
    mixer makes them) into slot 1 of a two-slot float32 pool, then one row a
    call as a decode step makes it (slot 0 idle: dt = 0), the state carried in
    the pool -> (y [T, d_inner], state [N, d_inner], whether the idle slot's
    state moved)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import selective_scan as ssm

    T, Dn = u.shape
    N = A.shape[0]
    tiles = ssm.channel_tiles
    pool = jnp.zeros((1, SLOTS, N, Dn // ssm.LANES, ssm.LANES), jnp.float32)
    # looked up when called, so that a departure that stands in front of the program's scan is seen
    chunked = jax.jit(lambda u, dt, b, c, pool: ssm.selective_scan(tiles(u)[None], tiles(dt)[None], b[None], c[None], tiles(A), tiles(D), pool, 0, jnp.int32(SLOT)), donate_argnums=(4,))
    stepped = jax.jit(lambda u, dt, b, c, pool: ssm.selective_scan(tiles(u)[:, None], tiles(dt)[:, None], b[:, None], c[:, None], tiles(A), tiles(D), pool, 0), donate_argnums=(4,))
    outs = []
    for start in range(0, prefill_len, chunk):
        n = min(chunk, prefill_len - start)
        pad = lambda a: jnp.zeros((chunk, *a.shape[1:]), a.dtype).at[:n].set(a[start : start + n])  # noqa: E731
        y, pool = chunked(pad(u), pad(dt), pad(Bm), pad(Cm), pool)
        outs.append(y[0, :n].reshape(n, Dn))
    for t in range(prefill_len, T):
        at_slot = lambda a: jnp.zeros((SLOTS, *a.shape[1:]), a.dtype).at[SLOT].set(a[t])  # noqa: E731
        y, pool = stepped(at_slot(u), at_slot(dt), at_slot(Bm), at_slot(Cm), pool)
        outs.append(y[SLOT].reshape(1, Dn))
    return jnp.concatenate(outs), pool[0, SLOT].reshape(N, Dn), bool(jnp.any(pool[0, 1 - SLOT]))


def reference_kwargs(c) -> dict:
    """What ``jamba_ref.forward`` is told of a ``JambaConfig``."""
    return dict(n_heads=c.n_heads, n_kv_heads=c.n_kv_heads, attn_layer_period=c.attn_layer_period, attn_layer_offset=c.attn_layer_offset,
                d_state=c.d_state, dt_rank=c.dt_rank, eps=c.norm_eps)


def compare(llm, prompt, *, page: int, chunk: int, ref_params=None, departures=(),
            kv_tol=KV_REL_TOL, kv_max_tol=KV_MAX_TOL, logit_tol=LOGIT_TOL, state_tol=STATE_REL_TOL, state_max_tol=STATE_MAX_TOL,
            window_tol=WINDOW_REL_TOL, rule_tol=RULE_TOL, rule_max_tol=RULE_MAX_TOL) -> dict:
    """The program (``llm``: a ``ShardedLLM`` of a ``JambaConfig``) against
    ``jamba_ref`` on one prompt.  The reference reads ``ref_params`` (default:
    the program's own weights).  Each of ``departures`` (``departure``'s
    names) is tried on the check that can see it, the recurrence alone, and
    reported under its name with the ``ok`` that check gives."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import jamba_ref as ref_mod

    c = llm.cfg
    plen = len(prompt)
    num_pages = pool_pages(plen, page)
    tokens, pool, table, _, _ = run_paged(llm.engine_programs(num_pages=num_pages, page_size=page, num_slots=SLOTS), llm.params, prompt, page=page, chunk=chunk, vocab=c.vocab_size)
    full = jnp.asarray(np.concatenate([prompt, tokens[:-1]]).astype(np.int32))  # every token that was fed
    rows = plen + DECODE_STEPS

    params32 = ref_params if ref_params is not None else llm.params
    ref = jax.jit(lambda p, t: ref_mod.forward(p, t, **reference_kwargs(c)))(params32, full)

    def rel(got, want):
        """(RMS, largest) error over the RMS of the reference."""
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        scale = np.sqrt((want**2).mean())
        return float(np.sqrt(((got - want) ** 2).mean()) / scale), float(np.abs(got - want).max() / scale)

    pos = np.arange(rows)
    got_k = np.asarray(pool[0].astype(jnp.float32))[:, table[pos // page], pos % page]
    got_v = np.asarray(pool[1].astype(jnp.float32))[:, table[pos // page], pos % page]
    (k_rms, k_max), (v_rms, v_max) = rel(got_k, ref.keys), rel(got_v, ref.values)
    state = np.asarray(pool[2]).reshape(pool[2].shape[0], SLOTS, c.d_state, c.d_inner)
    window = np.asarray(pool[3].astype(jnp.float32)).reshape(pool[3].shape[0], SLOTS, c.d_conv - 1, c.d_inner)
    per_layer = [rel(state[i, SLOT], ref.states[i]) for i in range(state.shape[0])]
    s_rms, s_max = max(e[0] for e in per_layer), max(e[1] for e in per_layer)
    w_rms = max(rel(window[i, SLOT], ref.windows[i])[0] for i in range(window.shape[0]))
    idle_touched = bool(np.any(state[:, 1 - SLOT]) or np.any(window[:, 1 - SLOT]))

    logits = np.asarray(ref.logits, np.float32)[:, : c.vocab_size]
    gaps = [float(logits[plen - 1 + j].max() - logits[plen - 1 + j, tok]) for j, tok in enumerate(tokens)]

    # the recurrence alone, on the reference's own inputs of the first layer
    p32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), {"emb": params32["tok_emb"], "norm": params32["ffn"]["attn_norm"][0],
                                                               "mp": jax.tree.map(lambda a: a[0], params32["mamba"])})
    with jax.default_matmul_precision("highest"):
        h0 = ref_mod._norm(p32["emb"][full], p32["norm"], c.norm_eps)
        u, dt, Bm, Cm, A, _, _ = jax.jit(lambda h, mp: ref_mod.scan_inputs(h, mp, d_state=c.d_state, dt_rank=c.dt_rank, eps=c.norm_eps))(h0, p32["mp"])
        y_ref, s_ref = jax.jit(ref_mod.selective_scan)(u, dt, Bm, Cm, A, p32["mp"]["D"])

    def alone():
        y_got, s_got, idle = rule_alone(u, dt, Bm, Cm, A, p32["mp"]["D"], chunk=chunk, prefill_len=plen)
        (y_rms, y_max), (st_rms, st_max) = rel(y_got, y_ref), rel(s_got, s_ref)
        found = {"rule_alone_err": max(y_rms, st_rms), "rule_alone_max_err": max(y_max, st_max)}
        return {**found, "ok": bool(found["rule_alone_err"] <= rule_tol and found["rule_alone_max_err"] <= rule_max_tol and not idle)}

    found = alone()
    narrowed = {}
    for which in departures:
        with departure(which):
            narrowed[which] = alone()

    out = {
        "layers": c.n_layers, "layer_kinds": "".join(kind[0] for kind in c.layer_kinds), "prompt_len": int(plen), "decode_steps": DECODE_STEPS,
        "chunks": -(-plen // chunk), "k_rel_err": k_rms, "v_rel_err": v_rms, "k_max_err": k_max, "v_max_err": v_max,
        "state_rel_err": s_rms, "state_max_err": s_max, "state_rel_err_by_layer": [e[0] for e in per_layer],
        "window_rel_err": w_rms, "state_dtype": str(pool[2].dtype), "idle_slot_touched": idle_touched,
        "rule_alone_err": found["rule_alone_err"], "rule_alone_max_err": found["rule_alone_max_err"],
        "logit_gap_max": max(gaps), "logit_std": float(logits[rows - 1].std()),
        "kv_tol": kv_tol, "kv_max_tol": kv_max_tol, "logit_tol": logit_tol, "state_tol": state_tol, "state_max_tol": state_max_tol,
        "window_tol": window_tol, "rule_tol": rule_tol, "rule_max_tol": rule_max_tol,
        "platform": jax.devices()[0].platform, **narrowed,
    }
    out["ok"] = bool(
        k_rms <= kv_tol and v_rms <= kv_tol and k_max <= kv_max_tol and v_max <= kv_max_tol
        and s_rms <= state_tol and s_max <= state_max_tol and w_rms <= window_tol and not idle_touched
        and out["state_dtype"] == "float32" and found["ok"] and max(gaps) <= logit_tol
    )
    return out


def _to_bf16(x):
    # reduce_precision, not a cast there and back: XLA may drop a pair of converts (excess precision), as the TPU's compiler does
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def fp8_weights(params):
    """``params`` with every matrix of a matmul rounded to float8_e4m3fn (the
    embedding, which is the head, among them; norm scales, biases, the conv
    kernel, A_log and D as they are): the nearest precision below bf16, which
    the through-the-pool limits must refuse."""
    import jax.numpy as jnp

    def rounded(tree, names):
        return {k: v.astype(jnp.float8_e4m3fn).astype(v.dtype) if k in names else v for k, v in tree.items()}

    return {**rounded(params, ("tok_emb",)), "mamba": rounded(params["mamba"], ("w_in", "w_x", "w_dt", "w_out")),
            "attn": rounded(params["attn"], ("wq", "wk", "wv", "wo")), "ffn": rounded(params["ffn"], ("w_gate", "w_up", "w_down"))}


@contextlib.contextmanager
def departure(which: str):
    """The program with one thing narrowed, for the length of the block:
    ``bf16_state`` (the recurrent state rounded to bf16 wherever a call hands
    it on, as a bf16 pool would).  It stands in front of the function the
    program looks up in its module when it is traced; programs built inside
    the block have it, programs built before do not."""
    from ray_tpu.ops import selective_scan as ssm

    if which != "bf16_state":
        raise ValueError(which)
    scan = ssm.selective_scan

    def narrowed(u, dt, Bm, Cm, A, D, state, *args, **kwargs):
        y, state = scan(u, dt, Bm, Cm, A, D, _to_bf16(state), *args, **kwargs)
        return y, _to_bf16(state)

    ssm.selective_scan = narrowed
    try:
        yield
    finally:
        ssm.selective_scan = scan


def reference_config(cfg: Mapping):
    """The program's configuration at the published widths over the file's
    ``reference_layers``: a few layers of both kinds."""
    import dataclasses

    ref = cfg["reference_layers"]
    return dataclasses.replace(jamba_config(cfg), n_layers=int(ref["num_hidden_layers"]), attn_layer_period=int(ref["attn_layer_period"]),
                               attn_layer_offset=int(ref["attn_layer_offset"]))


def _reference_check_in_worker(cfg: Mapping, seed: int) -> dict:
    import numpy as np

    from ray_tpu.serve.llm import ShardedLLM

    eng = cfg["engine"]
    lcfg = reference_config(cfg)
    llm = ShardedLLM(lcfg, tp=int(cfg["layout"]["tp"]), seed=seed % (2**31))
    chunk = int(eng["prefill_chunk"])
    plen = 2 * chunk + chunk // 3 + 5  # three chunks, the third partly padded
    prompt = np.random.default_rng(seed).integers(1, lcfg.vocab_size, plen).astype(np.int32)
    # what the isolated tolerance must refuse is tried in every traced run: the departure has to come out not ok
    out = compare(llm, prompt, page=int(eng["page_size"]), chunk=chunk, departures=("bf16_state",))
    out["as_published_ok"] = out["ok"]
    out["ok"] = bool(out["ok"] and not out["bf16_state"]["ok"])
    return out


def reference_check(cfg: Mapping, seed: int, chips: int) -> dict:
    """Traced runs only, before ``serve.run``, as ``drivers/serve.py`` does
    it: a TPU actor builds the program at the configuration's widths and
    ``reference_layers``, and is killed afterwards."""
    import ray_tpu

    @ray_tpu.remote(num_tpus=chips)
    class RefCheck:
        def run(self, cfg, seed):
            return _reference_check_in_worker(cfg, seed)

    actor = RefCheck.remote()
    try:
        return ray_tpu.get(actor.run.remote(dict(cfg), seed), timeout=900)
    finally:
        ray_tpu.kill(actor)


@contextlib.contextmanager
def _as_this_kind():
    """``drivers/serve.py`` with this kind's configuration builder and
    reference check, and ``serve_moe``'s stats-keeping client, for the length
    of the block.  This holds only while ``serve_moe.substituted`` looks
    ``moe_config`` and ``reference_check`` up as globals of its module when it
    executes.  What fails otherwise: ``benchmarks/tests/test_jamba2_cell.py``'s
    traced rehearsal, whose line must carry ``reference_check["layer_kinds"]``
    and ``state_bytes``."""
    saved = (serve_moe.moe_config, serve_moe.reference_check)
    serve_moe.moe_config, serve_moe.reference_check = jamba_config, reference_check
    try:
        with serve_moe.substituted():
            yield
    finally:
        serve_moe.moe_config, serve_moe.reference_check = saved


def run(ctx) -> dict:
    jamba_config(ctx.config)  # a program without the model fails here, before anything is started
    with _as_this_kind():
        raw = dense.run(ctx)
    seconds, counters = float(ctx.seconds), raw["counters"]
    start, log = raw["window_epoch"], serve_moe._Client.stats_log
    # what the replies said while the replica ran unhindered (``serve_moe.run``
    # says why): slots in the decode phase while the profiler captured, the rows
    # of the decode calls that the capture timed; slots in use from the window's
    # start to the capture's end
    lo = start + seconds / 3.0
    hi = lo + float(ctx.traffic.get("trace_seconds", 3.0))
    counters["slots_decode_samples"] = [r["slots_decode"] for asked, r in log if lo <= asked <= hi]
    counters["slots_active_unstalled"] = [r["slots_active"] for asked, r in log if start <= asked <= hi]
    counters["slots_active_unstalled_n"] = len(counters["slots_active_unstalled"])
    # the per-slot state the pool holds, and the chunks that began a sequence between the window's two ends
    ends = [min(log, key=lambda e: abs(e[0] - at))[1] for at in (start, start + seconds)] if log else []
    if len(ends) == 2 and all("state_bytes" in r for r in ends):
        counters["state_resets"] = ends[1]["state_resets"] - ends[0]["state_resets"]
        counters["state_bytes"] = ends[1]["state_bytes"]
    return raw
