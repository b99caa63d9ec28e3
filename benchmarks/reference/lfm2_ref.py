"""Plain reference of LFM2's expert decoder as LFM2-24B-A2B publishes it
(``model_type: lfm2_moe``; the mixers and the norms as transformers'
``modeling_lfm2.py`` has them: ``Lfm2ShortConv.slow_forward``,
``Lfm2Attention``, ``Lfm2DecoderLayer``; the expert block as
``Lfm2MoeSparseMoeBlock``), full causal forward of ONE sequence in
straightforward ``jax.numpy``, float32 at the highest matmul precision.  No
cache, no windows, no paging, no chunking, no batching of requests: the
convolution is a padded causal convolution over the whole sequence, attention
a [S, S] softmax, the experts a loop.

    norm(x; w) = w * x / sqrt(mean(x^2) + eps)
    x = x + mixer_i(norm(x; operator_norm));  x = x + ffn_i(norm(x; ffn_norm))

    Conv mixer (layer_types[i] == "conv"; E = hidden size, k = conv_L_cache = 3):
      [B | C | u] = h W_in                    three blocks of E, in that order
      z = B * u
      c(t) = w_0 * z(t-2) + w_1 * z(t-1) + w_2 * z(t)      depthwise, z = 0 before the sequence; no bias, no activation
      y = (C * c) W_out
      what a cache would keep after position t is [z(t-k+2) .. z(t)]: z is returned

    Attention mixer ("full_attention"; H query heads on KV key/value heads of D):
      q = h W_q -> [H, D];  k = h W_k, v = h W_v -> [KV, D]
      q = norm_D(q; q_layernorm),  k = norm_D(k; k_layernorm)     per head, BEFORE rotary
      rotary on all D dimensions, "rotate_half" pairs (i, i + D/2), angle t * theta^(-2i/D)
      causal softmax(q . k / sqrt(D)), each KV head serving H / KV query heads;  y = concat(o) W_o
      what a cache would keep of position s is k(s) (normed, rotated) and v(s): both returned

    FFN of the first ``n_dense`` layers: SwiGLU(g) = (silu(g W_1) * (g W_3)) W_2
    FFN of the others: sigma = sigmoid(g W_r), float32, over all X experts
      chosen = the K largest of sigma + b               (b: expert_bias; it chooses and never weighs)
      w = sigma[chosen] / (sum of them + 1e-6) * routed_scaling_factor        (norm_topk_prob)
      y = sum_{e in chosen} w_e SwiGLU_e(g)                                   no shared expert

    logits = norm(x; embedding_norm) W_emb^T        embedding_norm is the FINAL norm, despite its name

It reads the tree the program serves (``to_layers`` cuts the four stacks
into one dict a layer and changes no array: the program's layout IS the
published one, transposed as every ``nn.Linear`` is), because the comparison
is on the same weights.  ``forward`` runs a layer at a time, each in a
``jax.jit`` of its own that takes the layer's weights as stored and widens
them to float32 inside, so that a few layers at the published widths (2.4 GB
an expert layer in float32) pass through a 16 GB device one after the other.

Departures from the published model, each stated in the configuration's
``assumed`` too:

- the head is the embedding, transposed (the family's ``tie_embedding``
  default; the catalog's row does not carry the key);
- ``expert_bias`` is not zero (its published initial value) but drawn, so
  that it decides choices;
- the published code computes ``B * u``, the taps and ``C * c`` in the
  checkpoint's type (bfloat16); here they are float32 like everything else,
  and the program rounds ``z`` to bfloat16 and computes the taps and ``C * c``
  in float32;
- ties in the top-k go to the lower index (``lax.top_k``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

RENORM_EPS = 1e-6  # Lfm2MoeSparseMoeBlock: routing_weights / (routing_weights.sum(-1) + 1e-6)


class Forward(NamedTuple):
    logits: jax.Array  # [S, padded_vocab]
    keys: jax.Array  # [L_attn, S, KV, D] normed and rotated, as a cache would keep them
    values: jax.Array  # [L_attn, S, KV, D]
    z: jax.Array  # [L_conv, S, E] what each conv layer convolves: B * u
    mixer_in: jax.Array  # [L, S, E] the residual stream each mixer reads
    mixer_out: jax.Array  # [L, S, E] what each mixer adds to it
    ffn_in: jax.Array  # [L, S, E] the residual stream each FFN reads
    ffn_out: jax.Array  # [L, S, E] what each FFN adds to it
    scores: jax.Array  # [L_moe, S, X] sigmoid scores of all experts
    select: jax.Array  # [L_moe, S, X] scores + selection bias: what the top-k ranks
    chosen: jax.Array  # [L_moe, S, K] the experts used (the top-k, or ``routing``)


def _norm(x, w, eps):
    return x / jnp.sqrt((x**2).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, heads, D]: "rotate_half" over all D dimensions, position = row."""
    S, _, D = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : D // 2], x[..., D // 2 :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def short_conv(h, lp):
    """h [S, E] -> (out [S, E], z [S, E])."""
    S, E = h.shape
    bcu = h @ lp["w_in"]
    B, C, u = bcu[:, :E], bcu[:, E : 2 * E], bcu[:, 2 * E :]
    z = B * u
    k = lp["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, E), z.dtype), z])
    c = sum(lp["conv_w"][j] * padded[j : j + S] for j in range(k))
    return (C * c) @ lp["w_out"], z


def attention(h, lp, *, n_heads: int, n_kv_heads: int, rope_theta: float, eps: float):
    """h [S, E] -> (out [S, E], keys [S, KV, D], values [S, KV, D])."""
    S, E = h.shape
    D = E // n_heads
    q = _rope(_norm((h @ lp["wq"]).reshape(S, n_heads, D), lp["q_norm"], eps), rope_theta)
    k = _rope(_norm((h @ lp["wk"]).reshape(S, n_kv_heads, D), lp["k_norm"], eps), rope_theta)
    v = (h @ lp["wv"]).reshape(S, n_kv_heads, D)
    group = n_heads // n_kv_heads
    scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, group, axis=1)) / jnp.sqrt(jnp.float32(D))
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), jnp.repeat(v, group, axis=1)).reshape(S, E)
    return out @ lp["wo"], k, v


def swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def route(g, router_w, bias, top_k: int):
    """g [S, E] -> (sigma [S, X], sigma + bias [S, X], chosen [S, K]: the K largest of sigma + bias)."""
    with jax.default_matmul_precision("highest"):
        sigma = jax.nn.sigmoid(g.astype(jnp.float32) @ router_w.astype(jnp.float32))
    select = sigma + bias.astype(jnp.float32)
    return sigma, select, jax.lax.top_k(select, top_k)[1]


def routed_weights(sigma, used, *, norm_topk_prob: bool, routed_scaling_factor: float):
    """The weights of the experts ``used`` [S, K]: their own scores, the bias nowhere."""
    w = jnp.take_along_axis(sigma, used, axis=-1)
    if norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + RENORM_EPS)
    return w * routed_scaling_factor


def expert_ffn(g, weight, used, w_gate, w_up, w_down):
    """sum_{e in used} w_e SwiGLU_e(g), one expert at a time over the rows
    that chose it (a row that did not gets weight zero).  w_gate [X, E, H]."""

    def one(y, ew):
        e, wg, wu, wd = ew
        w_e = jnp.where(used == e, weight, 0.0).sum(-1)
        return y + w_e[:, None] * swiglu(g, wg, wu, wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(g), (jnp.arange(w_gate.shape[0]), w_gate, w_up, w_down))
    return y


def _layer(x, lp, routing, *, attn: dict, top_k: int, norm_topk_prob: bool, routed_scaling_factor: float, eps: float):
    """One layer on x [S, E]; ``lp`` as stored, widened here.  A conv layer
    has "conv_w" and no "wq"; a dense layer has no "router".  Returns (mid,
    mixer_out, ffn_out, kept: (keys, values) or z, routed: (sigma, select,
    used) or None)."""
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        h = _norm(x, lp["op_norm"], eps)
        if "conv_w" in lp:
            mixer_out, kept = short_conv(h, lp)
        else:
            mixer_out, *kept = attention(h, lp, eps=eps, **attn)
        mid = x + mixer_out
        g = _norm(mid, lp["ffn_norm"], eps)
        if "router" not in lp:
            return mid, mixer_out, swiglu(g, lp["w_gate"], lp["w_up"], lp["w_down"]), kept, None
        sigma, select, top = route(g, lp["router"], lp["router_bias"], top_k)
        used = top if routing is None else routing
        weight = routed_weights(sigma, used, norm_topk_prob=norm_topk_prob, routed_scaling_factor=routed_scaling_factor)
        return mid, mixer_out, expert_ffn(g, weight, used, lp["w_gate"], lp["w_up"], lp["w_down"]), kept, (sigma, select, used)


def forward(params, tokens, *, n_heads: int, n_kv_heads: int, rope_theta: float, eps: float, top_k: int, norm_topk_prob: bool,
            routed_scaling_factor: float, routing: Optional[jax.Array] = None) -> Forward:
    """tokens [S] -> ``Forward``.  ``params``: ``tok_emb`` [V, E],
    ``final_norm`` and ``layers``, a list of one dict a layer (``to_layers``):
    at least one conv layer, one attending layer and one layer of experts.
    ``routing`` [L_moe, S, K], if given, is used in place of each expert
    layer's own top-k (the weights stay the router's own scores of those
    experts), so that a comparison can hold the discrete choice fixed."""
    layer = jax.jit(functools.partial(_layer, attn=dict(n_heads=n_heads, n_kv_heads=n_kv_heads, rope_theta=rope_theta), top_k=top_k,
                                      norm_topk_prob=norm_topk_prob, routed_scaling_factor=routed_scaling_factor, eps=eps))
    x = params["tok_emb"][tokens].astype(jnp.float32)
    kept = {k: [] for k in ("keys", "values", "z", "mixer_in", "mixer_out", "ffn_in", "ffn_out", "scores", "select", "chosen")}
    n_moe = 0
    for lp in params["layers"]:
        sparse = "router" in lp
        mid, mixer_out, ffn_out, cached, routed = layer(x, lp, routing[n_moe] if sparse and routing is not None else None)
        if "conv_w" in lp:
            kept["z"].append(cached)
        else:
            kept["keys"].append(cached[0])
            kept["values"].append(cached[1])
        for key, val in (("mixer_in", x), ("mixer_out", mixer_out), ("ffn_in", mid), ("ffn_out", ffn_out)):
            kept[key].append(val)
        if sparse:
            for key, val in zip(("scores", "select", "chosen"), routed):
                kept[key].append(val)
            n_moe += 1
        x = mid + ffn_out

    @jax.jit
    def head(x, final_norm, tok_emb):
        with jax.default_matmul_precision("highest"):
            return _norm(x, final_norm.astype(jnp.float32), eps) @ tok_emb.astype(jnp.float32).T

    return Forward(head(x, params["final_norm"], params["tok_emb"]), *(jnp.stack(kept[k]) for k in kept))


def to_layers(params, layer_kinds, n_dense: int):
    """The tree the program serves (``Lfm2MoeModel.init``: stacks ``conv``,
    ``attn``, ``dense``, ``moe``) as the list of layers ``forward`` reads:
    layer i's mixer is the next of its kind (``layer_kinds[i]``: "conv" or
    "attn"), its FFN the next dense one while i < ``n_dense``, then the next
    of experts.  No array is changed."""
    layers, at = [], {"conv": 0, "attn": 0}
    for i, kind in enumerate(layer_kinds):
        mixer = {k: v[at[kind]] for k, v in params[kind].items()}
        at[kind] += 1
        stack, j = (params["dense"], i) if i < n_dense else (params["moe"], i - n_dense)
        layers.append({**mixer, **{k: v[j] for k, v in stack.items()}})
    return {"tok_emb": params["tok_emb"], "final_norm": params["final_norm"], "layers": layers}
