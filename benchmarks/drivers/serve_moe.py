"""A serving cell of a sparse-expert model (``kind: serve_moe``: OLMoE keys):
the same ``serve.run(engine_llm_deployment(...))`` replica, the same window,
the same judgement as ``drivers/serve.py``, whose ``run`` this driver calls.

What differs, and how it is put in without editing that file: ``serve.py``
looks up ``llama_config``, ``reference_check`` and ``Client`` as globals of its
module when ``run`` / ``deploy_and_warm`` execute (in this process), so
``substituted()`` swaps in

- ``moe_config``: the program's ``LlamaConfig`` with experts and QK-norm.  It
  is built FIRST in ``run``: a program without those keys (this PR's parent)
  raises ``TypeError`` here, before a replica, a TPU worker or a chip exists;
- ``reference_check``: the comparison with ``reference/olmoe_ref.py`` below;
- a ``Client`` that keeps every ``engine_stats`` reply, so that the routing
  counters of the two snapshots at the window's ends can be subtracted
  (``counters["moe_expert_load"]``: assignments per expert in the window),
  the slots in the decode phase that each reply reported while the profiler
  captured (``counters["slots_decode_samples"]``) and the slots in use up to
  the capture's end (``counters["slots_active_unstalled"]``: a traced run
  asks twice a second).

A later PR that adds a cell of this kind adds a traffic file of an existing
generator ``kind`` and BENCHMARK.json entries; the readers ``moe_*`` return
nothing for a configuration without ``num_experts``.
"""

from __future__ import annotations

import contextlib
import time
from typing import List, Mapping

from benchmarks.drivers import serve as dense
from benchmarks.drivers.serve import KV_MAX_TOL, KV_REL_TOL, LOGIT_TOL


_dense_config = dense.llama_config  # the dense builder itself: ``substituted()`` rebinds the module's name


def moe_config(cfg: Mapping):
    """The program's ``LlamaConfig`` for a configuration file with the
    published OLMoE keys (``model_type: olmoe``): the dense builder's (the
    shared keys; ``intermediate_size`` is one expert's width) plus experts
    and QK-norm, which is unconditional in olmoe."""
    import dataclasses

    if cfg.get("clip_qkv") or cfg.get("attention_bias") or cfg.get("norm_topk_prob"):
        raise ValueError("the program's block has no clip_qkv, no attention bias and no renormalised top-k weights")
    return dataclasses.replace(
        _dense_config(cfg), n_experts=cfg["num_experts"], n_experts_per_tok=cfg["num_experts_per_tok"], qk_norm=True,
    )


# ---- the comparison with the reference
#
# K/V and logit tolerances are the dense driver's (drivers/serve.py), for its
# reasons: the program computes in bf16.  They are applied with the reference
# GIVEN THE PROGRAM'S ROUTING, because a top-k is discrete: where the 8th and
# 9th probabilities lie closer than bf16 noise in the router's input, either
# choice is right, the two lead to different residual streams (at 2 layers of
# N(0, 0.02) weights one swapped expert moves a row by a quarter of its norm),
# and the reference must follow the program's to be comparable downstream.
# K/V, both tokens and the routing counter are those of the replica's own
# programs (``llm.engine_programs``: the pool's shardings with the counter as
# third member, donation, one compile each), as ``drivers/serve.py`` takes
# them.  They return no routing, so copies of the same two paged methods are
# jitted beside them that record each layer's choice as the trace passes
# through the model's one FFN (``routing_programs``); the copies are held to
# the engine programs' tokens, pool and counter by ``judge_copies`` below, so
# the routing handed to the reference is the routing behind the K/V that is
# compared.  What the choice itself is held to:
#
# - MARGIN: the reference, following the program's routing in the layers
#   before, has at each layer its own top-k; wherever its relative margin
#   (p_k - p_{k+1}) / p_k exceeds MARGIN the program's choice must be that
#   top-k.  On the chip at the published widths the program's choice differs
#   from that top-k in 3.4-4.8% of rows, the widest such flip at a margin of
#   0.012, 0.015 and 0.019 in three checks of 684 rows each (PERF.md section
#   6: noise of about 0.7% in a probability ratio, from bf16 rounding of the
#   router's input and of the layers before), so 0.03 is over 1.5 times the
#   worst sound flip; the other reading (weights rounded to fp8, the
#   nearest precision below the configuration's bf16) is in PERF.md section
#   6.  The share of rows under the margin ("near ties": a third of them,
#   because the 8th and 9th of 64 softmax probabilities lie 7% apart on
#   average) is reported.
# - ROUTER_TOL: the program's router (``parallel/moe.route``), given the
#   reference's own router inputs rounded to the compute type, returns the
#   reference's probabilities of its chosen experts within 1e-4 relative.  A
#   float32 softmax on either backend is within 1e-6; a bf16 softmax is off by
#   2^-9 = 2e-3, renormalised weights by a factor.
# - the routing counter that rides with the pool: its total is exact, and it
#   equals the count of the returned routing expert by expert but for what
#   ``judge_copies`` allows the two compiled forms.
ROUTER_TOL = 1e-4
MARGIN = 0.03


def rank_router(probs, top_k: int):
    """(experts by falling probability, stable; relative margin between the
    k-th and the (k+1)-th probability) of router probabilities [..., E]."""
    import numpy as np

    order = np.argsort(-probs, axis=-1, kind="stable")
    ranked = np.take_along_axis(probs, order[..., : top_k + 1], -1)
    return order, (ranked[..., top_k - 1] - ranked[..., top_k]) / ranked[..., top_k - 1]


def equal_outside(a, b, where) -> bool:
    """Whether two pool members are equal everywhere but at index ``where``."""
    import numpy as np

    differs = np.array(a != b)
    differs[where] = False
    return not differs.any()


def judge_copies(probs, routing, load, *, top_k: int, margin: float, expected_total: int, tokens, copy_tokens, token_rows,
                 pieces, rest_equal: bool = True, aside=()) -> dict:
    """What the copies that return the routing (``routing_programs``) are held
    to against the engine's own programs.  Pure: arrays in, a dict out.

    The two are different compiled forms of the same methods, and a top-k is
    discrete: in a row whose k-th and (k+1)-th probability lie closer than
    ``margin`` (relative; a "near tie") the forms may round to either of
    those two experts, exactly as the program and the reference may.  Nowhere
    else may they differ:

    - ``load`` (the engine's counter [E]) sums to ``expected_total`` exactly;
    - expert by expert it differs from the count of ``routing`` (the copies'
      [L, R, K], valid rows; with ``aside``: further ``(probs, routing)`` pairs
      of other sequences that went through the same counter) by no more than
      the near-tie rows allow: it may read HIGHER by the near-tie rows in which
      the expert is the reference's k-th or (k+1)-th choice (``probs`` [L, R,
      E]) and the copy left it out, LOWER by those in which the copy took it;
    - each of ``pieces`` ``(name, engine, copy, tol, max_tol)``, a pool member
      as the engine's programs and as the copies left it, agrees as the check
      asks the program to agree with the reference: the copy lies within
      ``tol`` (RMS) and ``max_tol`` (worst element) of the engine's, over the
      RMS of the engine's.  EQUAL they need not be: on the chip the forms
      round one key of the first layer an ulp apart at some seeds' weights,
      with nothing upstream of it, and attention hands that on to every later
      row (PERF.md section 6, PR 43);
    - ``tokens`` equal ``copy_tokens`` unless a near tie lies upstream of the
      row each was read from (``token_rows``, at the last layer).  The
      engine's own tokens are held to the reference by the caller;
    - ``rest_equal``: the caller found the pool equal outside ``pieces``.
    """
    import numpy as np

    probs, routing, load = np.asarray(probs), np.asarray(routing), np.asarray(load).astype(np.int64)
    n_experts = load.shape[0]
    order, rel_margin = rank_router(probs, top_k)
    tied = rel_margin <= margin  # [L, R]: the prompt's own rows, whose pool is compared below
    sequences = [(routing, order, rel_margin)]
    for p, r in aside:
        sequences.append((np.asarray(r), *rank_router(np.asarray(p), top_k)))
    # per expert, the margins of the near ties at which the counter may read higher (the expert is the row's k-th
    # or (k+1)-th choice and the copy left it out) or lower (the copy took it) than the count of the copies' routing
    counted = np.zeros(n_experts, np.int64)
    may_rise, may_fall = ([[] for _ in range(n_experts)] for _ in range(2))
    for r, order_, margin_ in sequences:
        counted += np.bincount(r.reshape(-1), minlength=n_experts)[:n_experts]
        for rank in (top_k - 1, top_k):
            expert = order_[..., rank]
            taken = (r == expert[..., None]).any(-1)
            for e, m, t in zip(expert[margin_ <= margin], margin_[margin_ <= margin], taken[margin_ <= margin]):
                (may_fall if t else may_rise)[e].append(float(m))
    delta = load - counted
    problems, widest = [], 0.0  # widest: the least margin under which near ties account for the counter, expert by expert
    if int(load.sum()) != int(expected_total):
        problems.append(f"counter total {int(load.sum())}, not {int(expected_total)}")
    for e in np.flatnonzero(delta):
        allowed = sorted((may_rise if delta[e] > 0 else may_fall)[e])
        if abs(delta[e]) > len(allowed):
            problems.append(f"counter off by {int(delta[e])} at expert {int(e)}, where near ties allow {len(allowed)}")
        else:
            widest = max(widest, allowed[abs(delta[e]) - 1])

    for name, engine, copy, tol, max_tol in pieces:
        engine, copy = np.asarray(engine, np.float32), np.asarray(copy, np.float32)
        scale = float(np.sqrt((engine**2).mean())) or 1.0
        rms, worst = float(np.sqrt(((copy - engine) ** 2).mean())) / scale, float(np.abs(copy - engine).max()) / scale
        if rms > tol or worst > max_tol:
            problems.append(f"{name}: the copies lie {rms:.3g} (RMS) / {worst:.3g} (worst) from the engine's programs, limits {tol} / {max_tol}")
    # a token may differ only downstream of a near tie: one in an earlier layer, in the row it was read from
    # or, through the mixers, in an earlier row
    n_layers = tied.shape[0]
    dirty = np.zeros((n_layers + 1, tied.shape[1]), bool)  # dirty[l, r]: a near tie lies upstream of what row r hands layer l; dirty[L]: of what the head reads
    for li in range(n_layers):
        dirty[li + 1] = np.logical_or.accumulate(dirty[li] | tied[li])
    for tok, copy_tok, row in zip(tokens, copy_tokens, token_rows):
        if tok != copy_tok and not dirty[n_layers, row]:
            problems.append(f"token after row {row}: {tok} against the copies' {copy_tok} with no near tie upstream")
    if len(tokens) != len(copy_tokens):
        problems.append(f"{len(tokens)} tokens against the copies' {len(copy_tokens)}")
    if not rest_equal:
        problems.append("the pools differ outside the rows the prompt wrote")
    return {"ok": not problems, "problems": problems, "flips": int(np.abs(delta).sum()) // 2, "flip_margin": widest, "near_tie_rows": int(tied.sum())}


def pool_pages(plen: int, page: int, slots: int = 2) -> int:
    """Pages of ``run_paged``'s pool: a prompt, one decoded token and a spare page a slot."""
    return slots * ((plen + page) // page + 1)


def run_paged(programs, params, prompt, *, page: int, chunk: int):
    """A prompt through ``prefill`` (chunks of ``chunk``, the last padded) and
    one ``decode`` step over a two-slot paged cache whose slot 1 owns the
    pool's first pages in reverse order; ``programs["init"]()`` makes the
    pool of ``pool_pages`` pages.  Returns (first token, second token, pool,
    slot 1's page table, routing [L, prompt + 1, K] or None if the programs
    return none)."""
    import numpy as np

    plen = len(prompt)
    slots = 2
    pages_per_slot = pool_pages(plen, page, slots) // slots
    pages = programs["init"]()
    tables = np.full((slots, pages_per_slot), -1, np.int32)
    tables[1] = np.arange(pages_per_slot, dtype=np.int32)[::-1]
    first, routing = None, []
    for start in range(0, plen, chunk):
        toks = np.zeros(chunk, np.int32)
        n_valid = min(chunk, plen - start)
        toks[:n_valid] = prompt[start : start + n_valid]
        first, pages, *chosen = programs["prefill"](params, pages, np.ascontiguousarray(tables[1]), toks, np.int32(start), np.int32(n_valid))
        routing += [np.asarray(c)[:, :n_valid] for c in chosen]
    first = int(first)
    tokens, positions, active = np.zeros(slots, np.int32), np.zeros(slots, np.int32), np.zeros(slots, bool)
    tokens[1], positions[1], active[1] = first, plen, True
    nxt, pages, *chosen = programs["decode"](params, pages, tables, tokens, positions, active)
    routing += [np.asarray(c)[:, 1:2] for c in chosen]
    return first, int(np.asarray(nxt)[1]), pages, tables[1], np.concatenate(routing, axis=1) if routing else None


def routing_programs(llm, num_pages: int, page: int):
    """Copies of the model's two paged methods, jitted, each with one more
    result: the chosen experts of every layer, [L, rows, K].  They are
    recorded while the method is traced, by standing in front of
    ``model._ffn`` (the one FFN every forward path calls; it returns ``(x,
    chosen [B, S, K])``), so the program needs no option for a comparison's
    sake.  ``compare`` uses them for the routing alone."""
    import functools

    import jax
    import jax.numpy as jnp

    model = llm.model

    def with_routing(method, rows):
        def traced(*args):
            chosen, ffn = [], model._ffn

            def recording(x, lp):
                out = ffn(x, lp)
                chosen.append(out[1])
                return out

            model._ffn = recording  # an instance attribute in front of the class's method
            try:
                out = method(*args)
            finally:
                del model._ffn
            return (*out, rows(jnp.stack(chosen)))

        return jax.jit(traced)

    return {
        "init": lambda: model.init_pages(num_pages, page),
        "prefill": with_routing(functools.partial(model.prefill_chunk_paged, page_size=page), lambda c: c[:, 0]),  # [L, 1, C, K]
        "decode": with_routing(functools.partial(model.decode_step_paged, page_size=page), lambda c: c[:, :, 0]),  # [L, S, 1, K]
    }


def compare(llm, prompt, *, page: int, chunk: int, ref_params=None, top_k=None,
            kv_tol=KV_REL_TOL, kv_max_tol=KV_MAX_TOL, logit_tol=LOGIT_TOL, margin=MARGIN, router_tol=ROUTER_TOL) -> dict:
    """The program (``llm``: a ``ShardedLLM``) against ``olmoe_ref`` on one
    prompt: prefill in chunks and one decode step through the paged cache,
    and the router alone.  The reference reads ``ref_params`` (default: the
    program's own weights) and routes ``top_k`` (default: the program's)
    experts a token, not renormalised: what the configuration publishes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import olmoe_ref
    from ray_tpu.parallel import moe

    lcfg = llm.cfg
    top_k = int(top_k or lcfg.n_experts_per_tok)
    plen = len(prompt)
    num_pages = pool_pages(plen, page)
    first, second, pages, table, _ = run_paged(llm.engine_programs(num_pages=num_pages, page_size=page), llm.params, prompt, page=page, chunk=chunk)
    *copy_tokens, copy_pages, _, routing = run_paged(routing_programs(llm, num_pages, page), llm.params, prompt, page=page, chunk=chunk)
    copy_differs = tuple(copy_tokens) != (first, second) or not all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(pages, copy_pages))
    full = jnp.asarray(np.concatenate([prompt, [first]]).astype(np.int32))

    pub = olmoe_ref.to_published_layout(ref_params if ref_params is not None else llm.params, lcfg.head_dim)
    ref_kw = dict(n_heads=lcfg.n_heads, n_kv_heads=lcfg.n_kv_heads, top_k=top_k, rope_theta=lcfg.rope_theta, eps=lcfg.norm_eps)
    same_k = routing.shape[-1] == top_k  # where the program chose another NUMBER of experts there is nothing to follow
    ref = jax.jit(lambda p, t, r: olmoe_ref.forward(p, t, routing=r, **ref_kw))(pub, full, jnp.asarray(routing) if same_k else None)
    probs = np.asarray(ref.router_probs)
    order, rel_margin = rank_router(probs, top_k)  # rel_margin [L, S]
    clear = rel_margin > margin
    agree = (np.sort(routing, -1) == np.sort(order[..., :top_k], -1)).all(-1) if same_k else np.zeros_like(clear)

    logits = np.asarray(ref.logits, np.float32)[:, : lcfg.vocab_size]
    pos = np.arange(plen + 1)
    where = (slice(None), table[pos // page], pos % page)  # of a K/V member [L, pages, page, ...]: what the prompt's rows wrote, [L, rows, ...]
    written = lambda member: np.asarray(member.astype(jnp.float32))[where]  # noqa: E731
    got_k, got_v = written(pages[0]), written(pages[1])

    def rel(got, want):
        """(RMS, largest) error over the RMS of the reference."""
        want = np.asarray(want, np.float32)
        scale = np.sqrt((want**2).mean())
        return float(np.sqrt(((got - want) ** 2).mean()) / scale), float(np.abs(got - want).max() / scale)

    (k_rms, k_max), (v_rms, v_max) = rel(got_k, olmoe_ref.keys_to_program_layout(ref.keys)), rel(got_v, ref.values)

    # the router alone: the program's function on the reference's inputs
    router_err, router_flips = 0.0, 0
    for li in range(lcfg.n_layers):
        h = jnp.asarray(ref.router_in[li]).astype(lcfg.compute_dtype)
        wr = llm.params["layers"]["router"][li]
        w_prog, c_prog = jax.jit(lambda h, w: moe.route(h, w, lcfg.n_experts_per_tok))(h, wr)
        p_ref, c_ref = olmoe_ref.route(h.astype(jnp.float32), wr, top_k)
        p_ref, w_prog, c_prog, c_ref = (np.asarray(a) for a in (p_ref, w_prog, c_prog, c_ref))
        if c_prog.shape != c_ref.shape:
            router_err, router_flips = float("inf"), c_ref.size
            break
        router_err = max(router_err, float(np.abs(w_prog / np.take_along_axis(p_ref, c_prog, -1) - 1.0).max()))
        r = -np.sort(-p_ref, -1)
        sure = (r[:, top_k - 1] - r[:, top_k]) / r[:, top_k - 1] > 10 * router_tol
        router_flips += int(((np.sort(c_prog, -1) != np.sort(c_ref, -1)).any(-1) & sure).sum())

    load = np.asarray(pages[2]).astype(np.int64)
    copies = judge_copies(
        probs, routing, load, top_k=top_k, margin=margin, expected_total=(plen + 1) * lcfg.n_layers * top_k,
        tokens=(first, second), copy_tokens=copy_tokens, token_rows=(plen - 1, plen),
        pieces=[("keys", got_k, written(copy_pages[0]), kv_tol, kv_max_tol), ("values", got_v, written(copy_pages[1]), kv_tol, kv_max_tol)],
        rest_equal=equal_outside(pages[0], copy_pages[0], where) and equal_outside(pages[1], copy_pages[1], where),
    ) if same_k else {"ok": False, "problems": ["another number of experts a token"], "flips": 0, "flip_margin": 0.0}
    out = {
        "layers": lcfg.n_layers, "prompt_len": int(plen), "experts": lcfg.n_experts, "top_k": top_k,
        "k_rel_err": k_rms, "v_rel_err": v_rms, "k_max_err": k_max, "v_max_err": v_max,
        "first_logit_gap": float(logits[plen - 1].max() - logits[plen - 1, first]),
        "second_logit_gap": float(logits[plen].max() - logits[plen, second]),
        "logit_std": float(logits[plen].std()),
        "routing_agreement": float(agree.mean()),  # rows whose chosen set is the reference's own top-k
        "routing_flips_above_margin": int((clear & ~agree).sum()),
        "near_tie_share": float(1.0 - clear.mean()),
        "flipped_margin_max": float(rel_margin[~agree].max()) if same_k and (~agree).any() else 0.0,
        "router_weight_err": router_err, "router_flips": router_flips,
        # the copies that returned the routing against the engine's programs: any difference at all in tokens or
        # pool; the assignments that went to a near tie's other expert; what ``judge_copies`` found outside what a near tie allows
        "routing_copy_differs": bool(copy_differs), "routing_copy_flips": copies["flips"], "routing_copy_problems": copies["problems"],
        "routing_copy_flip_margin": copies["flip_margin"],  # the least margin under which near ties account for every flip: at most ``margin``
        "moe_load_total": int(load.sum()),
        "moe_load_miscount": int(np.abs(load - np.bincount(routing.reshape(-1), minlength=lcfg.n_experts)).sum()),
        "kv_tol": kv_tol, "kv_max_tol": kv_max_tol, "logit_tol": logit_tol, "margin": margin, "router_tol": router_tol,
        "platform": jax.devices()[0].platform,
    }
    out["ok"] = bool(
        k_rms <= kv_tol and v_rms <= kv_tol and k_max <= kv_max_tol and v_max <= kv_max_tol
        and out["first_logit_gap"] <= logit_tol and out["second_logit_gap"] <= logit_tol
        and same_k and out["routing_flips_above_margin"] == 0
        and router_err <= router_tol and router_flips == 0 and copies["ok"]
    )
    return out


def _reference_check_in_worker(cfg: Mapping, seed: int) -> dict:
    import dataclasses

    import numpy as np

    from ray_tpu.serve.llm import ShardedLLM

    eng = cfg["engine"]
    lcfg = dataclasses.replace(moe_config(cfg), n_layers=int(cfg["reference_layers"]))
    llm = ShardedLLM(lcfg, tp=int(cfg["layout"]["tp"]), seed=seed % (2**31))
    chunk = int(eng["prefill_chunk"])
    plen = chunk + chunk // 3  # two chunks, the second partly padded
    prompt = np.random.default_rng(seed).integers(1, lcfg.vocab_size, plen).astype(np.int32)
    return compare(llm, prompt, page=int(eng["page_size"]), chunk=chunk)


def reference_check(cfg: Mapping, seed: int, chips: int) -> dict:
    """Traced runs only, before ``serve.run``, as ``drivers/serve.py`` does
    it: a TPU actor builds the program at the configuration's widths (all
    experts) and ``reference_layers`` layers, and is killed afterwards."""
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


class _Client(dense.Client):
    """``serve.py``'s client, keeping every ``engine_stats`` reply with the
    wall-clock instant it was asked for."""

    stats_log: List[tuple] = []

    def method(self, name: str, *args, timeout=None):
        asked = time.time()
        out = super().method(name, *args, timeout=timeout)
        if name == "engine_stats":
            _Client.stats_log.append((asked, out))
        return out


@contextlib.contextmanager
def substituted():
    """``drivers/serve.py`` with this kind's configuration builder, reference
    check and client in place of its own, for the length of the block.

    This holds only while ``serve.run`` and ``deploy_and_warm`` look the three
    names up as globals of their module when they execute; bound earlier (a
    default argument, a local import) they would serve a dense model unseen.
    What fails then: ``benchmarks/tests/test_olmoe_cells.py
    test_the_tiny_traced_rehearsal_of_the_new_cell`` -- its line must carry
    ``reference_check["experts"]`` (this kind's check ran), a positive
    ``moe_assignments`` (an expert replica answered, and this client kept
    its replies) and the slot samples.  PERF.md section 7 asks a ``benchmark``
    issue to make the three parameters of ``serve.run``."""
    names = {"llama_config": moe_config, "reference_check": reference_check, "Client": _Client}
    saved = {n: getattr(dense, n) for n in names}
    _Client.stats_log = []
    for n, v in names.items():
        setattr(dense, n, v)
    try:
        yield
    finally:
        for n, v in saved.items():
            setattr(dense, n, v)


SNAPSHOT_SLACK_S = 0.5  # the engine's gauge period: its routing counters are that stale in any reply


def run(ctx) -> dict:
    moe_config(ctx.config)  # a program without experts refuses the keys here, before anything is started
    with substituted():
        raw = dense.run(ctx)
    seconds, counters = float(ctx.seconds), raw["counters"]
    # the snapshots serve.py took at the window's two ends (the replies asked
    # for nearest those instants), for the per-expert assignments between
    # them.  Only a traced run's per-layer metric reads them, so only a traced
    # run is held to having them
    start = raw["window_epoch"]
    ends = [(at, *min(_Client.stats_log, key=lambda e: abs(e[0] - at))) for at in (start, start + seconds)]
    if all(abs(asked - at) <= SNAPSHOT_SLACK_S and "moe_expert_load" in reply for at, asked, reply in ends):
        (*_, s0), (*_, s1) = ends
        counters["moe_expert_load"] = [b - a for a, b in zip(s0["moe_expert_load"], s1["moe_expert_load"])]
        counters["moe_assignments"] = s1["moe_assignments"] - s0["moe_assignments"]
    elif ctx.trace:
        raw["problems"].append(f"no engine_stats reply with routing counters within {SNAPSHOT_SLACK_S} s of each end of the window")
        raw["correct"] = False
    # what the replies said while the replica ran unhindered.  ``serve.py``
    # starts the profiler a third into the window for the mix's
    # ``trace_seconds`` and then calls ``bench_trace_stop`` ON the replica,
    # which holds its intake for seconds while the profile is written: the
    # engine thread goes on, empties its queue and then its slots, so samples
    # taken after that instant (``counters["slot_samples"]`` has them all) say
    # nothing of a saturated replica.  A traced run asks twice a second.
    lo = start + seconds / 3.0
    hi = lo + float(ctx.traffic.get("trace_seconds", 3.0))
    # slots in the decode phase while the profiler captured: the rows of the decode calls that the capture timed
    counters["slots_decode_samples"] = [r["slots_decode"] for asked, r in _Client.stats_log if lo <= asked <= hi]
    # slots in use from the window's start to the capture's end
    counters["slots_active_unstalled"] = [r["slots_active"] for asked, r in _Client.stats_log if start <= asked <= hi]
    counters["slots_active_unstalled_n"] = len(counters["slots_active_unstalled"])  # a number, so the detail line keeps it
    return raw
