"""Plain reference of GPT-2 (Radford et al. 2019; ``openai-community/gpt2``
``modeling_gpt2.py``): forward pass, mean next-token cross entropy and its
gradients, in straightforward ``jax.numpy`` and float32.  No kernel, no remat,
no scan, no sharding: one Python loop over the layers.

It reads the parameter tree the program trains (``GPT2Model.init``: leaves
stacked over layers, the embedding padded to a multiple of 128 rows), because
the comparison is on the same weights.  Departures from the published model,
both the program's and copied here so that the numbers compare:
- the vocabulary is padded to a multiple of 128 rows; padded logits are
  excluded from the softmax;
- no dropout (the published config trains with 0.1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x**3)))


def forward(params, tokens, *, n_head: int, eps: float = 1e-5):
    """tokens [B, S] -> logits [B, S, padded_vocab], float32."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    B, S = tokens.shape
    x = p["wte"][tokens] + p["wpe"][:S][None]
    E = x.shape[-1]
    D = E // n_head
    mask = jnp.tril(jnp.ones((S, S), bool))
    lay = p["layers"]
    for i in range(lay["qkv_w"].shape[0]):
        h = _layer_norm(x, lay["ln1_scale"][i], lay["ln1_bias"][i], eps)
        qkv = h @ lay["qkv_w"][i] + lay["qkv_b"][i]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, S, n_head, D).transpose(0, 2, 1, 3)
        k = k.reshape(B, S, n_head, D).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, n_head, D).transpose(0, 2, 1, 3)
        scores = (q @ k.transpose(0, 1, 3, 2)) / jnp.sqrt(jnp.float32(D))
        scores = jnp.where(mask, scores, -jnp.inf)
        attn = jax.nn.softmax(scores, axis=-1) @ v
        attn = attn.transpose(0, 2, 1, 3).reshape(B, S, E)
        x = x + attn @ lay["proj_w"][i] + lay["proj_b"][i]
        h = _layer_norm(x, lay["ln2_scale"][i], lay["ln2_bias"][i], eps)
        h = _gelu_new(h @ lay["mlp_in_w"][i] + lay["mlp_in_b"][i])
        x = x + h @ lay["mlp_out_w"][i] + lay["mlp_out_b"][i]
    x = _layer_norm(x, p["ln_f"]["scale"], p["ln_f"]["bias"], eps)
    return x @ p["wte"].T


def loss(params, tokens, targets, *, n_head: int, vocab_size: int, eps: float = 1e-5):
    logits = forward(params, tokens, n_head=n_head, eps=eps)[..., :vocab_size]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


def loss_and_grad_norm(params, tokens, targets, *, n_head: int, vocab_size: int, eps: float = 1e-5):
    """(loss, global L2 norm of the gradients), float32 at the highest matmul
    precision (on a TPU a float32 matmul is otherwise done in bf16 passes)."""
    with jax.default_matmul_precision("highest"):
        val, grads = jax.value_and_grad(loss)(
            params, tokens, targets, n_head=n_head, vocab_size=vocab_size, eps=eps
        )
    sq = sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads))
    return val, jnp.sqrt(sq)
