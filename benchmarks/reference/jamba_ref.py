"""Plain reference of the Jamba decoder (transformers' ``modeling_jamba.py``;
``model_type: jamba``), full causal forward of ONE sequence in straightforward
``jax.numpy``, float32 at the highest matmul precision.  No cache, no paging,
no chunking, no batching of requests, no kernel: the Mamba mixer is
``JambaMambaMixer``'s slow path with the recurrence a per-token ``lax.scan``,
attention is a [S, S] softmax.

    norm(x; w) = w * x / sqrt(mean(x^2) + eps)
    x = x + mixer_i(norm(x; attn_norm));  x = x + ffn(norm(x; ffn_norm))
    layer i (from 0) attends where i % attn_layer_period == attn_layer_offset, else Mamba
    logits = norm(x; final_norm) tok_emb^T                                   tied head

    Mamba (d_inner = expand * hidden, N = d_state, R = dt_rank):
      [u | z] = h W_in
      u = silu(conv(u) + b_conv)            causal, depthwise, kernel d_conv, zeros before the sequence
      [dt_r | B | C] = u W_x;  dt_r, B, C <- norm(dt_r; dt_norm), norm(B; b_norm), norm(C; c_norm)
      dt = softplus(dt_r W_dt + b_dt);  A = -exp(A_log)
      h_0 = 0;  h[n, d] <- exp(dt[d] A[n, d]) h[n, d] + dt[d] B[n] u[d];  y[d] = sum_n h[n, d] C[n] + D[d] u[d]
      out = (y * silu(z)) W_out

    Attention (H query heads on KV heads of D = hidden / H; no positional encoding, no bias):
      out = softmax(q k^T / sqrt(D), causal) v W_o

    FFN: (silu(h W_gate) * (h W_up)) W_down

It reads the parameter tree the program serves (``JambaModel.init``: stacks
``mamba``, ``attn`` and ``ffn``; no ``out_head``), because the comparison is on
the same weights.

Departures from the published code, each stated in the configuration's
``assumed`` too:

- ``A_log`` is [N, d_inner] and the state [N, d_inner] (published: [d_inner,
  N]): the program's layout, d_inner on the lanes.  A loader transposes once;
  no result depends on it;
- the slow path casts the state to the model's dtype before the product with
  C (``ssm_state.to(dtype)``); here everything is float32;
- ``num_experts: 1``: ``expert_layer_period`` / ``expert_layer_offset`` select
  no layer, every FFN is the dense ``JambaMLP``;
- no dropout, no auxiliary loss, ``num_logits_to_keep`` ignored (all logits).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class Forward(NamedTuple):
    logits: jax.Array  # [S, padded_vocab]
    keys: jax.Array  # [L_attn, S, KV, D]
    values: jax.Array  # [L_attn, S, KV, D]
    states: jax.Array  # [L_mamba, N, d_inner] the state after the last token
    windows: jax.Array  # [L_mamba, k - 1, d_inner] the conv's last k - 1 inputs


def _norm(x, w, eps):
    return x / jnp.sqrt((x**2).mean(-1, keepdims=True) + eps) * w


def scan_inputs(h, mp, *, d_state: int, dt_rank: int, eps: float):
    """h [S, E] -> what the recurrence reads: u, dt [S, d_inner], B, C [S, N],
    A [N, d_inner]; and the gate z [S, d_inner] and the conv's last k - 1
    inputs [k - 1, d_inner]."""
    S = h.shape[0]
    d_inner = mp["w_in"].shape[1] // 2
    uz = h @ mp["w_in"]
    u, z = uz[:, :d_inner], uz[:, d_inner:]
    kernel = mp["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((kernel - 1, d_inner), h.dtype), u])
    u = jax.nn.silu(sum(padded[j : j + S] * mp["conv_w"][j] for j in range(kernel)) + mp["conv_b"])
    xdbc = u @ mp["w_x"]
    dt_r = _norm(xdbc[:, :dt_rank], mp["dt_norm"], eps)
    B = _norm(xdbc[:, dt_rank : dt_rank + d_state], mp["b_norm"], eps)
    C = _norm(xdbc[:, dt_rank + d_state :], mp["c_norm"], eps)
    dt = jax.nn.softplus(dt_r @ mp["w_dt"] + mp["b_dt"])
    return u, dt, B, C, -jnp.exp(mp["A_log"]), z, padded[S:]


def selective_scan(u, dt, B, C, A, D):
    """The recurrence, one token at a time from a zero state -> (y [S,
    d_inner], the state after the last token [N, d_inner])."""

    def token(h, row):
        u_t, dt_t, b_t, c_t = row
        h = jnp.exp(dt_t[None, :] * A) * h + (dt_t * u_t)[None, :] * b_t[:, None]
        return h, (h * c_t[:, None]).sum(0) + D * u_t

    h, y = jax.lax.scan(token, jnp.zeros_like(A), (u, dt, B, C))
    return y, h


def mamba(h, mp, *, d_state: int, dt_rank: int, eps: float):
    """h [S, E] -> (out [S, E], state [N, d_inner], window [k - 1, d_inner])."""
    u, dt, B, C, A, z, window = scan_inputs(h, mp, d_state=d_state, dt_rank=dt_rank, eps=eps)
    y, state = selective_scan(u, dt, B, C, A, mp["D"])
    return (y * jax.nn.silu(z)) @ mp["w_out"], state, window


def attention(h, ap, *, n_heads: int, n_kv_heads: int):
    """h [S, E] -> (out [S, E], keys [S, KV, D], values [S, KV, D])."""
    S, E = h.shape
    H, KV, D = n_heads, n_kv_heads, E // n_heads
    q = (h @ ap["wq"]).reshape(S, H, D)
    k = (h @ ap["wk"]).reshape(S, KV, D)
    v = (h @ ap["wv"]).reshape(S, KV, D)
    kk, vv = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, kk) / jnp.sqrt(jnp.float32(D))
    scores = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vv).reshape(S, E)
    return out @ ap["wo"], k, v


def forward(params, tokens, *, n_heads: int, n_kv_heads: int, attn_layer_period: int, attn_layer_offset: int,
            d_state: int, dt_rank: int, eps: float) -> Forward:
    """tokens [S] -> ``Forward``."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = p["tok_emb"][tokens]
        ffn = p["ffn"]
        keys, values, states, windows = [], [], [], []
        n_attn = n_mamba = 0
        for i in range(ffn["w_gate"].shape[0]):
            h = _norm(x, ffn["attn_norm"][i], eps)
            if i % attn_layer_period == attn_layer_offset:
                out, k, v = attention(h, jax.tree.map(lambda a: a[n_attn], p["attn"]), n_heads=n_heads, n_kv_heads=n_kv_heads)
                keys.append(k)
                values.append(v)
                n_attn += 1
            else:
                out, state, window = mamba(h, jax.tree.map(lambda a: a[n_mamba], p["mamba"]), d_state=d_state, dt_rank=dt_rank, eps=eps)
                states.append(state)
                windows.append(window)
                n_mamba += 1
            x = x + out
            h = _norm(x, ffn["ffn_norm"][i], eps)
            x = x + (jax.nn.silu(h @ ffn["w_gate"][i]) * (h @ ffn["w_up"][i])) @ ffn["w_down"][i]
        x = _norm(x, p["final_norm"], eps)
        return Forward(x @ p["tok_emb"].T, jnp.stack(keys), jnp.stack(values), jnp.stack(states), jnp.stack(windows))
