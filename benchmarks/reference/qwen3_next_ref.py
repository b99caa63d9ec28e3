"""Plain reference of the Qwen3-Next decoder (transformers'
``modeling_qwen3_next.py``; ``model_type: qwen3_next``), full causal forward
of ONE sequence in straightforward ``jax.numpy``, float32 at the highest
matmul precision.  No cache, no paging, no chunking, no batching of requests:
the Gated DeltaNet is the per-token recurrence in a ``lax.scan``, attention is
a [S, S] softmax, the experts are a loop.

    norm(x; w) = (1 + w) * x / sqrt(mean(x^2) + eps)            zero-centred RMSNorm
    x = x + mixer_i(norm(x; attn_norm));  x = x + moe(norm(x; ffn_norm))
    layer i (from 0) is full attention where (i + 1) % full_attention_interval == 0

    Gated DeltaNet (Hk key heads, Hv value heads, value head j on key head j // (Hv/Hk)):
      [q, k, v, z] = h W_qkvz;  [b, a] = h W_ba
      [q, k, v] = silu(conv([q, k, v]))     causal, depthwise, kernel 4, no bias, zeros before the sequence
      q = l2norm(q) / sqrt(Dk);  k = l2norm(k);  beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
      S_0 = 0;  S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);  S <- S + k_t d_t^T;  o_t = S^T q_t
      out = (w_norm * o_t / sqrt(mean(o_t^2) + eps) * silu(z_t)) W_out       per head, plain scale

    Gated attention (H query heads, KV heads, head_dim D, H*D != hidden):
      [query | gate] per head = h W_q;  k = h W_k;  v = h W_v
      query, k = norm over each head's D (q_norm, k_norm), then rotary on the first
      partial_rotary_factor * D dimensions, "rotate_half" pairs (i, i + rot/2), theta
      out = (softmax(query k^T / sqrt(D), causal) v * sigmoid(gate)) W_o

    Experts: p = softmax(h W_r) over ALL routed experts, float32; the top K; weights p_e / sum of
      the K (norm_topk_prob);  y = sum over the chosen experts HELD of w_e SwiGLU_e(h)
      + sigmoid(h w_sg) * SwiGLU_shared(h)

It reads the parameter tree the program serves (``Qwen3NextModel.init``:
stacks ``linear``, ``full`` and ``moe``; embedding and head padded to a
multiple of 128), because the comparison is on the same weights.

Departures from the published code, each stated in the configuration's
``assumed`` too:

- the held share of the experts: ``w_gate`` etc. hold experts
  ``expert_offset .. + X`` of the router's; what the absent experts would add
  is LEFT OUT, here as in the program, and that partial sum goes on to the
  next layer.  With all experts held this is the published layer;
- the fused projections' column order is the program's: ``w_qkvz`` = [q | k | v
  | z] with heads major within each (published: interleaved per key head),
  ``w_ba`` = [b | a].  A loader permutes columns once; no result depends on it;
- ties in the top-k go to the lower index (``lax.top_k``);
- no multi-token-prediction module (the published config has no key for it),
  no auxiliary loss, no dropout, no attention bias (none published).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp


class Forward(NamedTuple):
    logits: jax.Array  # [S, padded_vocab]
    keys: jax.Array  # [L_full, S, KV, D] rotated keys
    values: jax.Array  # [L_full, S, KV, D]
    states: jax.Array  # [L_lin, Hv, Dk, Dv] the recurrent state after the last token
    windows: jax.Array  # [L_lin, k - 1, channels] the conv's last k - 1 inputs
    router_in: jax.Array  # [L, S, E]
    router_probs: jax.Array  # [L, S, X] softmax over all routed experts
    chosen: jax.Array  # [L, S, K] the experts used (the top-k, or ``routing``)


def _norm(x, w, eps):
    return x / jnp.sqrt((x**2).mean(-1, keepdims=True) + eps) * (1.0 + w)


def _rope_partial(x, theta, rot: int):
    """x [S, heads, D]: "rotate_half" over the first ``rot`` dimensions."""
    S = x.shape[0]
    freqs = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : rot // 2], x[..., rot // 2 : rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def route(h, router_w, top_k: int):
    """h [S, E], router_w [E, X] -> (probs [S, X], chosen [S, K])."""
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(h.astype(jnp.float32) @ router_w.astype(jnp.float32), axis=-1)
    return probs, jax.lax.top_k(probs, top_k)[1]


def expert_ffn(h, probs, used, w_gate, w_up, w_down, *, offset: int, renormalize: bool):
    """The held experts' part of sum_{e in used} w_e SwiGLU_e(h), one expert
    at a time over all rows.  w_gate/w_up [X_held, E, H]; ``used`` [S, K]
    indexes the router's experts; held expert j is the router's offset + j."""
    weight = jnp.take_along_axis(probs, used, axis=-1)
    if renormalize:
        weight = weight / weight.sum(-1, keepdims=True)

    def one(y, ew):
        e, wg, wu, wd = ew
        w_e = jnp.where(used == offset + e, weight, 0.0).sum(-1)
        return y + w_e[:, None] * ((jax.nn.silu(h @ wg) * (h @ wu)) @ wd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (jnp.arange(w_gate.shape[0]), w_gate, w_up, w_down))
    return y


def delta_inputs(h, lp, *, key_heads: int, value_heads: int, key_dim: int, value_dim: int):
    """h [S, E] -> what the recurrence reads, per value head: q, k [S, Hv,
    Dk], v [S, Hv, Dv], g, beta [S, Hv]; and the gate z [S, Hv, Dv] and the
    conv's last k - 1 inputs [k - 1, channels]."""
    S = h.shape[0]
    Hk, Hv, Dk, Dv = key_heads, value_heads, key_dim, value_dim
    conv_dim = 2 * Hk * Dk + Hv * Dv
    qkvz = h @ lp["w_qkvz"]
    mixed, z = qkvz[:, :conv_dim], qkvz[:, conv_dim:]
    ba = h @ lp["w_ba"]
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ba[:, Hv:] + lp["dt_bias"])
    kernel = lp["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((kernel - 1, conv_dim), h.dtype), mixed])
    y = jax.nn.silu(sum(padded[j : j + S] * lp["conv_w"][j] for j in range(kernel)))

    def l2(x):
        return x / jnp.sqrt((x**2).sum(-1, keepdims=True) + 1e-6)

    rep = Hv // Hk
    q = jnp.repeat(l2(y[:, : Hk * Dk].reshape(S, Hk, Dk)) / jnp.sqrt(jnp.float32(Dk)), rep, axis=1)
    k = jnp.repeat(l2(y[:, Hk * Dk : 2 * Hk * Dk].reshape(S, Hk, Dk)), rep, axis=1)
    v = y[:, 2 * Hk * Dk :].reshape(S, Hv, Dv)
    return q, k, v, g, beta, z.reshape(S, Hv, Dv), padded[S:]


def delta_rule(q, k, v, g, beta):
    """The recurrence, one token at a time from a zero state -> (o [S, Hv,
    Dv], the state after the last token [Hv, Dk, Dv])."""

    def token(state, qkvgb):
        q_t, k_t, v_t, g_t, b_t = qkvgb
        state = state * jnp.exp(g_t)[:, None, None]
        d = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    state, o = jax.lax.scan(token, jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), q.dtype), (q, k, v, g, beta))
    return o, state


def gated_delta_net(h, lp, *, eps: float, **heads):
    """h [S, E] -> (out [S, E], state [Hv, Dk, Dv], window [k - 1, channels])."""
    q, k, v, g, beta, z, window = delta_inputs(h, lp, **heads)
    o, state = delta_rule(q, k, v, g, beta)
    o = o / jnp.sqrt((o**2).mean(-1, keepdims=True) + eps) * lp["out_norm"]
    return (o * jax.nn.silu(z)).reshape(h.shape[0], -1) @ lp["w_out"], state, window


def gated_attention(h, fp, *, n_heads: int, n_kv_heads: int, head_dim: int, rot: int, theta: float, eps: float):
    """h [S, E] -> (out [S, E], rotated keys [S, KV, D], values [S, KV, D])."""
    S = h.shape[0]
    H, KV, D = n_heads, n_kv_heads, head_dim
    qg = (h @ fp["wq"]).reshape(S, H, 2, D)
    q, gate = qg[:, :, 0], qg[:, :, 1].reshape(S, H * D)
    k = (h @ fp["wk"]).reshape(S, KV, D)
    v = (h @ fp["wv"]).reshape(S, KV, D)
    q = _rope_partial(_norm(q, fp["q_norm"], eps), theta, rot)
    k = _rope_partial(_norm(k, fp["k_norm"], eps), theta, rot)
    kk, vv = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, kk) / jnp.sqrt(jnp.float32(D))
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vv).reshape(S, H * D)
    return (attn * jax.nn.sigmoid(gate)) @ fp["wo"], k, v


def forward(params, tokens, *, n_heads: int, n_kv_heads: int, head_dim: int, partial_rotary_factor: float, rope_theta: float,
            eps: float, full_attention_interval: int, top_k: int, norm_topk_prob: bool, expert_offset: int,
            lin_key_heads: int, lin_value_heads: int, lin_key_dim: int, lin_value_dim: int,
            routing: Optional[jax.Array] = None) -> Forward:
    """tokens [S] -> ``Forward``.  ``routing`` [L, S, K], if given, is used in
    place of each layer's own top-k (the weights stay the router's own
    probabilities of those experts), so that a comparison can hold the discrete
    choice fixed."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = p["tok_emb"][tokens]
        moe = p["moe"]
        keys, values, states, windows, router_in, router_probs, chosen = [], [], [], [], [], [], []
        n_full = n_lin = 0
        for i in range(moe["router"].shape[0]):
            h = _norm(x, moe["attn_norm"][i], eps)
            if (i + 1) % full_attention_interval == 0:
                fp = jax.tree.map(lambda a: a[n_full], p["full"])
                out, k, v = gated_attention(h, fp, n_heads=n_heads, n_kv_heads=n_kv_heads, head_dim=head_dim,
                                            rot=int(head_dim * partial_rotary_factor), theta=rope_theta, eps=eps)
                keys.append(k)
                values.append(v)
                n_full += 1
            else:
                lp = jax.tree.map(lambda a: a[n_lin], p["linear"])
                out, state, window = gated_delta_net(h, lp, key_heads=lin_key_heads, value_heads=lin_value_heads,
                                                     key_dim=lin_key_dim, value_dim=lin_value_dim, eps=eps)
                states.append(state)
                windows.append(window)
                n_lin += 1
            x = x + out

            h = _norm(x, moe["ffn_norm"][i], eps)
            probs, top = route(h, moe["router"][i], top_k)
            used = top if routing is None else routing[i]
            y = expert_ffn(h, probs, used, moe["w_gate"][i], moe["w_up"][i], moe["w_down"][i], offset=expert_offset, renormalize=norm_topk_prob)
            shared = (jax.nn.silu(h @ moe["ws_gate"][i]) * (h @ moe["ws_up"][i])) @ moe["ws_down"][i]
            x = x + y + jax.nn.sigmoid(h @ moe["shared_gate"][i]) * shared
            router_in.append(h)
            router_probs.append(probs)
            chosen.append(used)
        x = _norm(x, p["final_norm"], eps)
        return Forward(x @ p["out_head"], jnp.stack(keys), jnp.stack(values), jnp.stack(states), jnp.stack(windows),
                       jnp.stack(router_in), jnp.stack(router_probs), jnp.stack(chosen))
