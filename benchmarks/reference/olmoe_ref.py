"""Plain reference of the OLMoE decoder (Muennighoff et al. 2024;
``transformers`` ``modeling_olmoe.py``), full causal forward in straightforward
``jax.numpy`` and float32 at the highest matmul precision.  No cache, no
paging, no batching of requests; a loop over the experts.

    h  = RMSNorm(x; attn_norm)
    q  = RMSNorm(h Wq; q_norm)        k = RMSNorm(h Wk; k_norm)     v = h Wv
         (QK-norm over the whole projection, before the split into heads;
          unconditional in olmoe: no config key says so)
    q, k = rotary(q), rotary(k)       per head, "rotate_half": pairs (i, i + D/2)
    x  = x + softmax(q k^T / sqrt(D), causal) v Wo
    h  = RMSNorm(x; ffn_norm)
    p  = softmax(h Wr) over ALL experts, in float32
    S  = the top-k of p; weights p_e, not renormalised (norm_topk_prob false)
    x  = x + sum_{e in S} p_e * (silu(h Wg_e) * (h Wu_e)) Wd_e      no capacity, no drop
    logits = RMSNorm(x_L; final_norm) W_head

It reads the parameter tree the program serves (``LlamaModel.init`` with
experts and ``qk_norm``: leaves stacked over layers, experts stacked within a
layer; embedding and head padded to a multiple of 128), because the comparison
is on the same weights.

Departures from ``modeling_olmoe.py``, each noted in the configuration's
``assumed`` too:

- rotary pairing.  The program rotates interleaved pairs (2i, 2i+1), the
  published code pairs (i, i + D/2).  The two are the same function of weights
  whose ``wq``/``wk`` columns (and ``q_norm``/``k_norm`` entries) are permuted
  within each head, and attention scores do not see a permutation applied to
  queries and keys alike.  This file applies the PUBLISHED pairing; the
  comparison hands it ``to_published_layout(params)`` -- the permuted tree, the
  same conversion a checkpoint loader applies the other way round -- and
  brings the keys it returns back with ``keys_to_program_layout``;
- ties in the top-k go to the lower expert index (``lax.top_k``; ``torch.topk``
  leaves the order of equal values open);
- no auxiliary load-balancing loss (training only), no ``clip_qkv`` (null in
  the published config), no attention bias or dropout (none published).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class Forward(NamedTuple):
    logits: jax.Array  # [S, padded_vocab]
    keys: jax.Array  # [L, S, KV, D] rotated keys, published column order
    values: jax.Array  # [L, S, KV, D]
    router_in: jax.Array  # [L, S, E] what each layer's router was given
    router_probs: jax.Array  # [L, S, X] softmax over all experts
    chosen: jax.Array  # [L, S, K] the experts used (the top-k, or ``routing``)


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x**2).mean(-1, keepdims=True) + eps) * scale


def _rope_half(x, theta):
    """x [S, heads, D]: rotate each pair (i, i + D/2) by pos * theta^(-2i/D)
    (``rotate_half``: x*cos + cat(-x2, x1)*sin)."""
    S, _, D = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : D // 2], x[..., D // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def route(h, router_w, top_k: int):
    """h [S, E] float32, router_w [E, X] -> (probs [S, X], chosen [S, K]):
    softmax over all experts, then the top-k by probability."""
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(h.astype(jnp.float32) @ router_w.astype(jnp.float32), axis=-1)
    return probs, jax.lax.top_k(probs, top_k)[1]


def expert_ffn(h, probs, used, w_gate, w_up, w_down):
    """sum over a row's experts ``used`` [S, K] of p_e * (silu(h Wg_e) *
    (h Wu_e)) Wd_e, one expert at a time over all rows: a row gets every one
    of its experts whatever the other rows chose.  h [S, E]; probs [S, X];
    w_gate/w_up [X, E, H]; w_down [X, H, E]."""
    weight = jnp.take_along_axis(probs, used, axis=-1)  # [S, K], not renormalised
    y = jnp.zeros_like(h)
    for e in range(w_gate.shape[0]):
        w_e = jnp.where(used == e, weight, 0.0).sum(-1)  # [S]: p_e where e was chosen, else 0
        y = y + w_e[:, None] * ((jax.nn.silu(h @ w_gate[e]) * (h @ w_up[e])) @ w_down[e])
    return y


def _head_permutation(width: int, head_dim: int) -> np.ndarray:
    """Column j of the published layout is column perm[j] of the program's:
    within each head, the even columns first, then the odd ones."""
    within = np.concatenate([np.arange(0, head_dim, 2), np.arange(1, head_dim, 2)])
    return (np.arange(0, width, head_dim)[:, None] + within[None]).reshape(-1)


def to_published_layout(params, head_dim: int):
    """The program's tree with ``wq``/``wk`` columns and ``q_norm``/``k_norm``
    entries permuted so that the published rotary pairing on it computes what
    the program's interleaved pairing computes on the original."""
    lay = dict(params["layers"])
    for w, n in (("wq", "q_norm"), ("wk", "k_norm")):
        perm = _head_permutation(lay[w].shape[-1], head_dim)
        lay[w], lay[n] = lay[w][..., perm], lay[n][..., perm]
    return {**params, "layers": lay}


def keys_to_program_layout(keys):
    """Keys [..., D] of the published layout, in the program's column order."""
    D = keys.shape[-1]
    return keys[..., np.argsort(_head_permutation(D, D))]


def forward(params, tokens, *, n_heads: int, n_kv_heads: int, top_k: int, rope_theta: float, eps: float,
            routing: Optional[jax.Array] = None) -> Forward:
    """tokens [S] -> ``Forward``.  ``routing`` [L, S, K], if given, is used in
    place of each layer's own top-k (the weights stay the router's own
    probabilities of those experts), so that a comparison can hold the
    discrete choice fixed."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        (S,) = tokens.shape
        x = p["tok_emb"][tokens]
        E = x.shape[-1]
        D = E // n_heads
        rep = n_heads // n_kv_heads
        mask = jnp.tril(jnp.ones((S, S), bool))
        lay = p["layers"]
        keys, values, router_in, router_probs, chosen = [], [], [], [], []
        for i in range(lay["wq"].shape[0]):
            h = _rms_norm(x, lay["attn_norm"][i], eps)
            q = _rms_norm(h @ lay["wq"][i], lay["q_norm"][i], eps)
            k = _rms_norm(h @ lay["wk"][i], lay["k_norm"][i], eps)
            q = _rope_half(q.reshape(S, n_heads, D), rope_theta)
            k = _rope_half(k.reshape(S, n_kv_heads, D), rope_theta)
            v = (h @ lay["wv"][i]).reshape(S, n_kv_heads, D)
            keys.append(k)
            values.append(v)
            kk, vv = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
            scores = jnp.einsum("qhd,khd->hqk", q, kk) / jnp.sqrt(jnp.float32(D))
            scores = jnp.where(mask[None], scores, -jnp.inf)
            attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vv)
            x = x + attn.reshape(S, E) @ lay["wo"][i]

            h = _rms_norm(x, lay["ffn_norm"][i], eps)
            probs, top = route(h, lay["router"][i], top_k)
            used = top if routing is None else routing[i]
            x = x + expert_ffn(h, probs, used, lay["w_gate"][i], lay["w_up"][i], lay["w_down"][i])
            router_in.append(h)
            router_probs.append(probs)
            chosen.append(used)
        x = _rms_norm(x, p["final_norm"], eps)
        return Forward(x @ p["out_head"], jnp.stack(keys), jnp.stack(values),
                       jnp.stack(router_in), jnp.stack(router_probs), jnp.stack(chosen))
