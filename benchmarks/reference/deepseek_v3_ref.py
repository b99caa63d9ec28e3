"""Plain reference of the DeepSeek-V3 decoder block as Moonlight-16B-A3B
publishes it (``model_type: deepseek_v3``; transformers'
``modeling_deepseek_v3.py``), full causal forward of ONE sequence in
straightforward ``jax.numpy``, float32 at the highest matmul precision.  No
cache, no paging, no chunking, no batching of requests, and the attention
UNABSORBED: every position's per-head keys and values are expanded from its
latent, scores are a [S, S] softmax, the experts are a loop.

    norm(x; w) = w * x / sqrt(mean(x^2) + eps)
    x = x + mla(norm(x; attn_norm));  x = x + ffn_i(norm(x; ffn_norm))

    Latent attention (H heads; r = kv_lora_rank, dn / dr = qk_nope / qk_rope_head_dim, dv = v_head_dim):
      q = h W_q -> per head [q_nope (dn) | q_rope (dr)];  q_rope = rope(q_rope, t)
      [c (r) | k_rope (dr)] = h W_dkv;  c = norm(c; kv_norm);  k_rope = rope(k_rope, t)     one k_rope for all heads
      [k_nope_h (dn) | v_h (dv)] = c W_ukv  per head
      s_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_rope(s)) / sqrt(dn + dr),  causal softmax
      out = concat_h(sum_s p_h(t, s) v_h(s)) W_o
      rope: "rotate_half" pairs (i, i + dr/2), angle t * theta^(-2i/dr)
      what a cache would keep of position s is the row [c(s) | k_rope(s)]: returned as ``rows``

    FFN of the first ``first_k_dense`` layers: SwiGLU(h) = (silu(h W_g) * (h W_u)) W_d
    FFN of the others: sigma = sigmoid(h W_r), float32, over all X routed experts;
      chosen = the K largest of sigma + b      (b: e_score_correction_bias; one group, so no group limit)
      w = sigma[chosen] / (sum of them + 1e-20) * routed_scaling_factor      (norm_topk_prob)
      y = sum_{e in chosen} w_e SwiGLU_e(h) + SwiGLU_shared(h)               shared: one SwiGLU of n_shared x width, unweighted

It reads weights in the PUBLISHED layout (``to_published_layout`` re-lays the
tree the program serves: ``kv_b_proj`` whole, [r, H * (dn + dv)] with each
head's [k_nope | v] together), because the comparison is on the same weights.
``forward`` runs a layer at a time, each in a ``jax.jit`` of its own that
takes the layer's weights as stored and widens them to float32 inside, so that
three layers at the published widths (2.3 GB a layer in float32) pass through
a 16 GB device one after the other.

Departures from the published model, each stated in the configuration's
``assumed`` too:

- rotary is applied in the "rotate_half" layout to q_rope and k_rope as the
  weights give them.  The published code first permutes the checkpoint's
  interleaved pairs (2i, 2i + 1) into that layout (``apply_rotary_pos_emb_interleave``);
  a loader does that once to the columns of W_q and W_dkv, random weights do
  not see it;
- ties in the top-k go to the lower index (``lax.top_k``);
- no multi-token-prediction module (``num_nextn_predict_layers`` 0: the config
  has none), no ``q_lora_rank`` (null), no rope scaling (null), ``n_group`` 1;
  no auxiliary loss, dropout or attention bias (none published).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class Forward(NamedTuple):
    logits: jax.Array  # [S, padded_vocab]
    rows: jax.Array  # [L, S, r + dr] what a cache keeps of each position: [c | k_rope]
    attn_in: jax.Array  # [L, S, E] the residual stream each mixer reads
    attn_out: jax.Array  # [L, S, E] what each mixer adds to it
    ffn_in: jax.Array  # [L, S, E] the residual stream each FFN reads
    ffn_out: jax.Array  # [L, S, E] what each FFN adds to it
    scores: jax.Array  # [L_moe, S, X] sigmoid scores of all routed experts
    select: jax.Array  # [L_moe, S, X] scores + selection bias: what the top-k ranks
    chosen: jax.Array  # [L_moe, S, K] the experts used (the top-k, or ``routing``)


def _norm(x, w, eps):
    return x / jnp.sqrt((x**2).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, heads, dr]: "rotate_half" over all dr dimensions, position = row."""
    S, _, dr = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, dr, 2, dtype=jnp.float32) / dr))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : dr // 2], x[..., dr // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def latent_attention(h, lp, *, n_heads: int, kv_lora_rank: int, qk_nope_head_dim: int, qk_rope_head_dim: int, v_head_dim: int,
                     rope_theta: float, eps: float):
    """h [S, E] -> (out [S, E], rows [S, r + dr])."""
    S = h.shape[0]
    H, r, dn, dr, dv = n_heads, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim
    q = (h @ lp["wq"]).reshape(S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], rope_theta)
    ckr = h @ lp["w_dkv"]
    c = _norm(ckr[:, :r], lp["kv_norm"], eps)
    k_rope = _rope(ckr[:, None, r:], rope_theta)  # [S, 1, dr]
    kv = (c @ lp["w_ukv"]).reshape(S, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope) + jnp.einsum("qhd,kd->hqk", q_rope, k_rope[:, 0])) / jnp.sqrt(jnp.float32(dn + dr))
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v).reshape(S, H * dv)
    return out @ lp["wo"], jnp.concatenate([c, k_rope[:, 0]], axis=-1)


def swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def route(h, router_w, bias, top_k: int):
    """h [S, E] -> (sigma [S, X], sigma + bias [S, X], chosen [S, K]: the K largest of sigma + bias)."""
    with jax.default_matmul_precision("highest"):
        sigma = jax.nn.sigmoid(h.astype(jnp.float32) @ router_w.astype(jnp.float32))
    select = sigma + bias.astype(jnp.float32)
    return sigma, select, jax.lax.top_k(select, top_k)[1]


def routed_weights(sigma, used, *, norm_topk_prob: bool, routed_scaling_factor: float):
    """The weights of the experts ``used`` [S, K]: their own scores, the bias nowhere."""
    w = jnp.take_along_axis(sigma, used, axis=-1)
    if norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * routed_scaling_factor


def expert_ffn(h, weight, used, w_gate, w_up, w_down):
    """sum_{e in used} w_e SwiGLU_e(h), one expert at a time over the rows
    that chose it (a row that did not gets weight zero).  w_gate [X, E, H]."""

    def one(y, ew):
        e, wg, wu, wd = ew
        w_e = jnp.where(used == e, weight, 0.0).sum(-1)
        return y + w_e[:, None] * swiglu(h, wg, wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (jnp.arange(w_gate.shape[0]), w_gate, w_up, w_down))
    return y


def _layer(x, lp, routing, *, attention: dict, top_k: int, norm_topk_prob: bool, routed_scaling_factor: float, eps: float):
    """One layer on x [S, E]; ``lp`` as stored, widened here.  A dense layer
    has no "router"; its routing results are None."""
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        attn_out, rows = latent_attention(_norm(x, lp["attn_norm"], eps), lp, eps=eps, **attention)
        mid = x + attn_out
        h = _norm(mid, lp["ffn_norm"], eps)
        if "router" not in lp:
            return mid, attn_out, swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), rows, None
        sigma, select, top = route(h, lp["router"], lp["router_bias"], top_k)
        used = top if routing is None else routing
        weight = routed_weights(sigma, used, norm_topk_prob=norm_topk_prob, routed_scaling_factor=routed_scaling_factor)
        y = expert_ffn(h, weight, used, lp["w_gate"], lp["w_up"], lp["w_down"]) + swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        return mid, attn_out, y, rows, (sigma, select, used)


def forward(params, tokens, *, n_heads: int, kv_lora_rank: int, qk_nope_head_dim: int, qk_rope_head_dim: int, v_head_dim: int,
            rope_theta: float, eps: float, top_k: int, norm_topk_prob: bool, routed_scaling_factor: float,
            routing: Optional[jax.Array] = None) -> Forward:
    """tokens [S] -> ``Forward``.  ``params``: ``tok_emb`` [V, E], ``out_head``
    [E, V], ``final_norm`` and ``layers``, a list of one dict a layer in the
    published layout (``to_published_layout``).  ``routing`` [L_moe, S, K], if
    given, is used in place of each expert layer's own top-k (the weights
    stay the router's own scores of those experts), so that a comparison can
    hold the discrete choice fixed."""
    attention = dict(n_heads=n_heads, kv_lora_rank=kv_lora_rank, qk_nope_head_dim=qk_nope_head_dim, qk_rope_head_dim=qk_rope_head_dim,
                     v_head_dim=v_head_dim, rope_theta=rope_theta)
    layer = jax.jit(functools.partial(_layer, attention=attention, top_k=top_k, norm_topk_prob=norm_topk_prob,
                                      routed_scaling_factor=routed_scaling_factor, eps=eps))
    x = params["tok_emb"][tokens].astype(jnp.float32)
    kept = {k: [] for k in ("rows", "attn_in", "attn_out", "ffn_in", "ffn_out", "scores", "select", "chosen")}
    n_moe = 0
    for lp in params["layers"]:
        sparse = "router" in lp
        mid, attn_out, ffn_out, rows, routed = layer(x, lp, routing[n_moe] if sparse and routing is not None else None)
        for key, val in (("rows", rows), ("attn_in", x), ("attn_out", attn_out), ("ffn_in", mid), ("ffn_out", ffn_out)):
            kept[key].append(val)
        if sparse:
            for key, val in zip(("scores", "select", "chosen"), routed):
                kept[key].append(val)
            n_moe += 1
        x = mid + ffn_out

    @jax.jit
    def head(x, final_norm, out_head):
        with jax.default_matmul_precision("highest"):
            return _norm(x, final_norm.astype(jnp.float32), eps) @ out_head.astype(jnp.float32)

    return Forward(head(x, params["final_norm"], params["out_head"]), *(jnp.stack(kept[k]) for k in kept))


def to_published_layout(params, first_k_dense: int):
    """The tree the program serves (``DeepseekV3Model.init``: stacks ``attn``,
    ``dense``, ``moe``; ``kv_b_proj`` split by head into ``w_uk`` [H, dn, r]
    and ``w_uv`` [H, r, dv]) as the list of layers ``forward`` reads, with
    ``w_ukv`` [r, H * (dn + dv)] whole as published."""
    attn, layers = params["attn"], []
    for i in range(attn["wq"].shape[0]):
        ap = {k: v[i] for k, v in attn.items() if k not in ("w_uk", "w_uv")}
        uk, uv = attn["w_uk"][i], attn["w_uv"][i]  # [H, dn, r], [H, r, dv]
        ap["w_ukv"] = jnp.concatenate([uk.transpose(2, 0, 1), uv.transpose(1, 0, 2)], axis=-1).reshape(uk.shape[2], -1)
        stack, j = (params["dense"], i) if i < first_k_dense else (params["moe"], i - first_k_dense)
        layers.append({**ap, **{k: v[j] for k, v in stack.items()}})
    return {"tok_emb": params["tok_emb"], "out_head": params["out_head"], "final_norm": params["final_norm"], "layers": layers}
