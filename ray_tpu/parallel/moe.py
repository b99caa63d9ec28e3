"""Sparse-expert FFNs.  Two layers live here: the top-1 switch layer with
all-to-all dispatch over the `ep` axis described next (``moe_ffn``, training,
``models/gpt2.py``'s ``moe_experts``), and below it an exact dropless top-k
layer of gated experts (``dropless_moe_ffn``: ``models/llama.py`` OLMoE,
``qwen3_next.py``, ``deepseek_v3.py``), its router a softmax or a sigmoid
with a selection bias.

Expert parallelism: a capability absent from the reference (SURVEY §2.4 "Expert parallel
(EP/MoE): absent") — built the TPU way: experts shard over the `ep` mesh
axis, tokens route to experts via `lax.all_to_all` (one ICI all-to-all
each way), top-1 switch routing with capacity dropping (Switch
Transformer; see PAPERS.md).

Per-device shapes under shard_map: tokens [B_local, S, E]; each device
hosts n_experts/ep_size experts.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def moe_ffn(
    x: jax.Array,  # [tokens_local, E] per device
    router_w: jax.Array,  # [E, n_experts]
    expert_in: jax.Array,  # [experts_local, E, H]
    expert_out: jax.Array,  # [experts_local, H, E]
    *,
    axis_name: str = "ep",
    capacity_factor: float = 1.25,
) -> jax.Array:
    """Top-1 routed expert FFN.  Runs inside shard_map over `axis_name`."""
    ep = lax.psum(1, axis_name)
    n_tokens, E = x.shape
    experts_local = expert_in.shape[0]
    n_experts = ep * experts_local
    capacity = max(1, int(capacity_factor * n_tokens / n_experts))

    logits = x @ router_w  # [T, n_experts]
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)  # [T]
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]

    # position of each token within its expert's queue; drop beyond capacity
    one_hot = jax.nn.one_hot(expert_idx, n_experts, dtype=jnp.int32)  # [T, X]
    pos_in_expert = (jnp.cumsum(one_hot, axis=0) - 1) * one_hot  # [T, X]
    pos = pos_in_expert.max(axis=1)  # [T]
    keep = pos < capacity

    # dispatch buffer: [n_experts, capacity, E]
    dispatch = jnp.zeros((n_experts, capacity, E), x.dtype)
    dispatch = dispatch.at[expert_idx, jnp.where(keep, pos, 0)].add(
        jnp.where(keep[:, None], x, 0.0)
    )
    # all-to-all: expert dim split across devices, each device gets its
    # experts' tokens from every peer → [ep, experts_local, capacity, E]
    shaped = dispatch.reshape(ep, experts_local, capacity, E)
    received = lax.all_to_all(shaped, axis_name, split_axis=0, concat_axis=0)
    # [ep(peer), experts_local, capacity, E] → per expert: [ep*capacity, E]
    tokens_per_expert = received.transpose(1, 0, 2, 3).reshape(
        experts_local, ep * capacity, E
    )

    # expert FFN (batched over local experts — one MXU matmul pair)
    h = jax.nn.gelu(jnp.einsum("xte,xeh->xth", tokens_per_expert, expert_in))
    y = jnp.einsum("xth,xhe->xte", h, expert_out)

    # route back: inverse all-to-all
    y = y.reshape(experts_local, ep, capacity, E).transpose(1, 0, 2, 3)
    returned = lax.all_to_all(y, axis_name, split_axis=0, concat_axis=0)
    combined = returned.reshape(n_experts, capacity, E)

    out = combined[expert_idx, jnp.where(keep, pos, 0)]
    out = jnp.where(keep[:, None], out * gate[:, None], 0.0)
    return out


def make_moe_ffn(mesh, *, axis_name: str = "ep", capacity_factor: float = 1.25):
    """shard_map wrapper: tokens sharded over `ep` (data-style), experts
    sharded over `ep` (their leading dim)."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import shard_map_compat

    fn = functools.partial(moe_ffn, axis_name=axis_name, capacity_factor=capacity_factor)
    return shard_map_compat(
        fn,
        mesh,
        in_specs=(
            P(axis_name, None),
            P(None, None),
            P(axis_name, None, None),
            P(axis_name, None, None),
        ),
        out_specs=P(axis_name, None),
    )


# ----------------------------------------------------- dropless top-k (serving)


def route(h: jax.Array, router_w: jax.Array, top_k: int):
    """The router of a dropless top-k layer, in float32 whatever ``h`` is:
    softmax over ALL experts, then the ``top_k`` largest probabilities.
    h [T, E]; router_w [E, X].  Returns (weights [T, K] float32, chosen
    [T, K] int32); the weights are the softmax's own values, NOT divided by
    their sum (``dropless_moe_ffn`` does that where a model publishes
    ``norm_topk_prob`` true)."""
    logits = jnp.dot(
        h.astype(jnp.float32), router_w.astype(jnp.float32), precision=lax.Precision.HIGHEST
    )
    probs = jax.nn.softmax(logits, axis=-1)
    return lax.top_k(probs, top_k)


def route_sigmoid(h: jax.Array, router_w: jax.Array, top_k: int, bias=None):
    """``route`` for DeepSeek-V3's family: the score of an expert is the
    sigmoid of its logit alone, float32 as there.  ``bias`` [X], a selection
    bias (``e_score_correction_bias``), is added to the scores for the CHOICE
    alone: the weights returned are the scores themselves, not divided by
    their sum."""
    logits = jnp.dot(
        h.astype(jnp.float32), router_w.astype(jnp.float32), precision=lax.Precision.HIGHEST
    )
    scores = jax.nn.sigmoid(logits)
    if bias is None:
        return lax.top_k(scores, top_k)
    _, chosen = lax.top_k(scores + bias.astype(jnp.float32), top_k)
    return jnp.take_along_axis(scores, chosen, axis=-1), chosen


def reads_touched_experts_only(rows: int, top_k: int, n_experts: int) -> bool:
    """The one rule by which ``dropless_moe_ffn`` picks its form, from what a
    call's shapes say: ``rows * top_k`` assignments over a router of
    ``n_experts`` touch about ``1 - exp(-rows * top_k / n_experts)`` of the
    held experts, whichever share is held.  Swept on a v5e at Qwen3-Next's
    widths (128 of 512 held, top 10, every row live; PERF.md section 6, PR
    48) the masked contraction costs 1.09 ms a layer whatever the rows, 8.5 us
    an expert, and a visit of the touched form 13.9 us (its three matmuls, in
    two fusions, move 6 MB at two thirds of the bandwidth): the forms meet
    where 0.61 of the held experts are touched, at ``rows * top_k`` about
    ``n_experts``.  The rule takes the touched form up to half of that, where
    it still costs 0.55 of the masked form (24 rows: 0.58 against 1.09 ms),
    and beyond it leaves the layer to the contraction whose cost no routing
    moves."""
    return 2 * rows * top_k <= n_experts


def dropless_moe_ffn(
    h: jax.Array,  # [T, E]
    router_w: jax.Array,  # [E, X]
    w_gate: jax.Array,  # [X, E, H]; with ``layer``, a model's whole stack [L, X, E, H]
    w_up: jax.Array,  # [X, E, H]
    w_down: jax.Array,  # [X, H, E]
    *,
    top_k: int,
    renormalize: bool = False,
    expert_offset: int = 0,
    scoring: str = "softmax",
    bias=None,
    scale: float = 1.0,
    renorm_eps: Optional[float] = None,
    valid=None,
    layer: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact top-k routed SwiGLU experts: every row goes through all
    ``top_k`` of its experts whatever the other rows chose -- no capacity,
    nothing dropped -- and a row's result does not depend on its neighbours.

    The layer is told which experts it HOLDS: the router scores all X
    experts of ``router_w``, and ``w_gate``/``w_up``/``w_down`` are those of
    experts ``expert_offset .. expert_offset + w_gate.shape[0] - 1``, all of
    them (OLMoE, Moonlight) or one device's share of an expert-parallel
    deployment (Qwen3-Next: 128 of 512).  The result is the held experts'
    part of the sum; the shares of all holders add up to the whole layer, and
    a row none of whose choices is held here gets zero.  ``scoring`` says
    which router scores them, ``route`` ("softmax") or ``route_sigmoid``
    ("sigmoid", with its optional selection ``bias``); ``renormalize``
    divides a row's ``top_k`` weights by their sum (``norm_topk_prob``: OLMoE
    false, Qwen3-Next and Moonlight true) and ``scale`` multiplies them after
    that (``routed_scaling_factor``).  ``renorm_eps`` is what a model's
    published code adds to that sum (LFM2: 1e-6); left out, it is 1e-20
    under the sigmoid (DeepSeek-V3's) and nothing under the softmax.  A shared
    expert is the model's to add.

    One layer in two forms, which share the router block to its last line and
    part at ``dense_w`` [T, held], a row's weight for each held expert and zero
    where it did not choose it.  ``reads_touched_experts_only`` picks by the
    call's shapes; the engine's calls, and their choices over the router's
    experts:

        Qwen3-Next decode   16 rows x 10 of 512   0.31   the touched experts
        Qwen3-Next chunk   256 rows x 10 of 512   5      masked
        OLMoE decode        32 rows x  8 of  64   4      masked
        Moonlight decode    64 rows x  6 of  64   6      masked
        LFM2 decode         96 rows x  4 of  64   6      masked

    The masked contraction over ALL held experts: every one's SwiGLU runs on
    every row, and the router's weight multiplies the activation before ONE
    down-projection contracts over experts and expert width together.  At
    OLMoE's shapes (32 decode rows, 256 chunk rows, 64 experts, top 8)
    every expert is chosen by some row, so every expert's weights are read
    from HBM whichever way it is computed; masking costs X/K times the
    routed FLOPs and needs no sort, no gather and no data-dependent shape.
    On a v5e at OLMoE's widths (8 layers; PERF.md section 5, PR 28) the 32
    decode rows' masked FLOPs are 1.0 ms at peak against 7.9 ms of weight
    bytes, and the contractions read their weights at about 85% of the HBM
    bandwidth.  A 256-row chunk's are 8.4 ms at peak against the same 7.9
    ms of bytes: AT the roofline, not hidden under it (the chunk program
    takes 12.7 ms, 67% of its byte roofline), so from that row count on a
    grouped matmul over the routed rows alone would be the faster layer.

    The touched form (``_touched_experts_ffn``), for a decode step of a few
    rows over a wide router: 16 rows' 160 choices fall on ~19 of the 128
    experts held, and the masked form would stream all 128 (805 MB a layer
    for 118 MB used).  It visits the touched experts alone, in ascending
    order, each visit reading ONE expert's three matrices.  ``valid`` [T]
    bool says which rows are live (a decode step's idle slots are not): a
    row that is not touches nothing and gets zero; the masked form, which
    reads every expert anyway, takes no notice of it.  It needs the stacks
    where they lie: ``layer`` says that ``w_gate``/``w_up``/``w_down`` are a
    model's WHOLE stacks [L, X, E, H] and this call is layer ``layer`` of
    them.  A layer's slice handed to the loop is a value the TPU compiler
    materialises in front of it -- all three stacks copied, every call, which
    costs more than the masked form reads -- so a call with sliced stacks
    keeps the masked form at every shape (OLMoE's, Moonlight's and LFM2's
    models slice; their few-slot deployments lose nothing they had).
    Returns (y [T, E] in h's dtype, chosen [T, K])."""
    cd = h.dtype
    n_experts, held = router_w.shape[-1], w_gate.shape[-3]
    with jax.named_scope("router"):
        if scoring == "sigmoid":
            weights, chosen = route_sigmoid(h, router_w, top_k, bias)
        elif scoring == "softmax" and bias is None:
            weights, chosen = route(h, router_w, top_k)
        else:
            raise ValueError(f"scoring={scoring!r} with bias={bias is not None}: softmax, or sigmoid with an optional selection bias")
        if renormalize:  # over the row's top_k, held here or not
            total = weights.sum(-1, keepdims=True)
            if renorm_eps is None:
                # sigmoid scores can all underflow to zero; a softmax's top_k cannot (the published codes differ here too)
                renorm_eps = 1e-20 if scoring == "sigmoid" else 0.0
            weights = weights / (total + renorm_eps if renorm_eps else total)
        if scale != 1.0:
            weights = weights * scale
        # [T, X]: a row's weight for each expert, zero where not chosen
        dense_w = (jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32) * weights[..., None]).sum(-2)
        if held != n_experts:
            dense_w = dense_w[:, expert_offset : expert_offset + held]
    if layer is not None and reads_touched_experts_only(h.shape[0], top_k, n_experts):
        if valid is not None:
            dense_w = dense_w * valid[:, None]
        return _touched_experts_ffn(h, dense_w, w_gate, w_up, w_down, layer), chosen
    if layer is not None:
        w_gate, w_up, w_down = w_gate[layer], w_up[layer], w_down[layer]
    gate = jnp.einsum("te,xeh->xth", h, w_gate.astype(cd))
    up = jnp.einsum("te,xeh->xth", h, w_up.astype(cd))
    act = (jax.nn.silu(gate) * up).astype(jnp.float32) * dense_w.T[:, :, None]
    y = jnp.einsum("xth,xhe->te", act.astype(cd), w_down.astype(cd), preferred_element_type=jnp.float32)
    return y.astype(cd), chosen


def _touched_experts_ffn(h, dense_w, w_gate, w_up, w_down, layer: int):
    """``dropless_moe_ffn`` from ``dense_w`` [T, held] on, reading the
    weights of the held experts some row has a weight for and no others.
    The list of those experts has a static length (``held``) and a dynamic
    count: a loop of that many visits takes them in ascending order, and a
    visit cuts ONE expert out of each stack [L, held, ., .] at ``(layer,
    expert)`` (the compiler reads the cut in place, as its matmul's operand),
    runs all T rows through its SwiGLU, scales by that expert's column of
    ``dense_w`` and adds into a float32 [T, E].  No shape depends on the
    data.  A row's non-zero terms arrive in ascending expert order whoever
    else is in the call, and another row's expert adds an exact zero to it:
    alone or among others, a row's result is the same to the bit."""
    cd = h.dtype
    touched = (dense_w != 0).any(0)  # [held]
    order = jnp.argsort(~touched, stable=True)  # the touched experts first, ascending

    def visit(i, y):
        x = order[i]

        def one(w):
            return lax.dynamic_slice(w, (layer, x, 0, 0), (1, 1) + w.shape[-2:]).reshape(w.shape[-2:]).astype(cd)

        act = (jax.nn.silu(h @ one(w_gate)) * (h @ one(w_up))).astype(jnp.float32) * lax.dynamic_slice_in_dim(dense_w, x, 1, axis=1)
        return y + jnp.dot(act.astype(cd), one(w_down), preferred_element_type=jnp.float32)

    y = lax.fori_loop(0, touched.sum(), visit, jnp.zeros(h.shape, jnp.float32))
    return y.astype(cd)
