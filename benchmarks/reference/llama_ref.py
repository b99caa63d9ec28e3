"""Plain reference of the Mistral-7B decoder (Jiang et al. 2023,
``mistral-inference`` ``model.py``; the same block equations as Llama:
RMSNorm, rotary embeddings on interleaved pairs as the published code applies
them, grouped-query attention, SwiGLU), full causal forward in straightforward
``jax.numpy`` and float32.  No cache, no paging, no batching of requests.

It reads the parameter tree the program serves (``LlamaModel.init``: leaves
stacked over layers; embedding and head padded to a multiple of 128), because
the comparison is on the same weights.  No sliding window (v0.3 has none).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt((x**2).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [S, heads, D]: rotate each pair (2i, 2i+1) by pos * theta^(-2i/D)."""
    S, _, D = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def forward(params, tokens, *, n_heads: int, n_kv_heads: int, rope_theta: float, eps: float):
    """tokens [S] -> (logits [S, padded_vocab], keys, values), float32 at the
    highest matmul precision.  ``keys``/``values`` [L, S, KV, D] are each
    layer's rotated keys and values: what a correct cache holds."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        (S,) = tokens.shape
        x = p["tok_emb"][tokens]
        E = x.shape[-1]
        D = E // n_heads
        rep = n_heads // n_kv_heads
        mask = jnp.tril(jnp.ones((S, S), bool))
        lay = p["layers"]
        keys, values = [], []
        for i in range(lay["wq"].shape[0]):
            h = _rms_norm(x, lay["attn_norm"][i], eps)
            q = _rope((h @ lay["wq"][i]).reshape(S, n_heads, D), rope_theta)
            k = _rope((h @ lay["wk"][i]).reshape(S, n_kv_heads, D), rope_theta)
            v = (h @ lay["wv"][i]).reshape(S, n_kv_heads, D)
            keys.append(k)
            values.append(v)
            kk, vv = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
            scores = jnp.einsum("qhd,khd->hqk", q, kk) / jnp.sqrt(jnp.float32(D))
            scores = jnp.where(mask[None], scores, -jnp.inf)
            attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vv)
            x = x + attn.reshape(S, E) @ lay["wo"][i]
            h = _rms_norm(x, lay["ffn_norm"][i], eps)
            x = x + (jax.nn.silu(h @ lay["w_gate"][i]) * (h @ lay["w_up"][i])) @ lay["w_down"][i]
        x = _rms_norm(x, p["final_norm"], eps)
        return x @ p["out_head"], jnp.stack(keys), jnp.stack(values)
