"""Op-level numerics: the hand-written kernels must match their reference
compositions exactly (fused CE custom VJP vs naive full-logits path)."""

import numpy as np
import pytest


def test_fused_ce_matches_naive_loss_and_grads():
    """The fused linear-head CE (ops/cross_entropy.py custom VJP) must
    reproduce the naive [B,S,V]-materializing path: loss and every
    parameter gradient.  Guards the hand-written backward (chunk order,
    g/(B*S) scale, pad-vocab masking)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt2 import GPT2Config, GPT2Model

    cfg_f = GPT2Config.tiny(compute_dtype=jnp.float32, loss_impl="fused", loss_chunk=16)
    cfg_n = GPT2Config.tiny(compute_dtype=jnp.float32, loss_impl="naive")
    m_f, m_n = GPT2Model(cfg_f), GPT2Model(cfg_n)
    params = m_f.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg_f.vocab_size)
    tgts = jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, cfg_f.vocab_size)

    lf, gf = jax.value_and_grad(lambda p: m_f.loss(p, toks, tgts))(params)
    ln, gn = jax.value_and_grad(lambda p: m_n.loss(p, toks, tgts))(params)
    np.testing.assert_allclose(float(lf), float(ln), rtol=1e-6)
    for (path_f, leaf_f), (_, leaf_n) in zip(
        jax.tree_util.tree_leaves_with_path(gf),
        jax.tree_util.tree_leaves_with_path(gn),
    ):
        np.testing.assert_allclose(
            np.asarray(leaf_f), np.asarray(leaf_n), rtol=1e-4, atol=1e-6,
            err_msg=f"grad mismatch at {path_f}",
        )


def test_fused_ce_uneven_chunk():
    """Sequence length not divisible by the requested chunk falls back to a
    dividing chunk size without changing the result."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.cross_entropy import fused_linear_cross_entropy

    B, S, E, V = 2, 48, 16, 64  # 48 % 32 != 0 → falls to chunk 16
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, S, E), jnp.float32)
    w = jax.random.normal(key, (V, E), jnp.float32)
    t = jax.random.randint(key, (B, S), 0, 60)

    fused = fused_linear_cross_entropy(x, w, t, 60, 32)
    logits = jnp.where(jnp.arange(V) >= 60, -1e30, x @ w.T)
    naive = (
        jax.nn.logsumexp(logits, -1)
        - jnp.take_along_axis(logits, t[..., None], -1)[..., 0]
    ).mean()
    np.testing.assert_allclose(float(fused), float(naive), rtol=1e-6)


def test_splash_attention_over_a_mesh_matches_reference():
    """XLA cannot partition the Mosaic kernel, so over a mesh the splash path
    places it with a shard_map (batch over dp/fsdp, heads over tp).  The
    Pallas interpreter stands in for the chip: forward and gradients agree
    with the einsum reference on every mesh, and the cached kernel object
    survives a second jit (its mask arrays are not a first trace's tracers)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops import attention as A
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh

    B, S, H, D = 4, 128, 2, 64
    q, k, v = (
        jax.random.normal(key, (B, S, H, D), jnp.float32)
        for key in jax.random.split(jax.random.PRNGKey(0), 3)
    )
    scale = D**-0.5
    ref = A._xla_causal_attention(q, k, v, scale)
    ref_grad = jax.grad(lambda *a: (A._xla_causal_attention(*a, scale) ** 2).sum())(q, k, v)
    for cfg in (None, MeshConfig(dp=2, fsdp=2, tp=2)):
        mesh = make_mesh(cfg, jax.devices()) if cfg else None
        args = (q, k, v)
        if mesh is not None:
            sharding = NamedSharding(mesh, P(("dp", "fsdp")))
            args = tuple(jax.device_put(a, sharding) for a in args)

        def splash(*a):
            return A._splash_causal_attention(*a, scale, mesh, interpret=True)

        out = jax.jit(splash)(*args)
        np.testing.assert_allclose(out, ref, atol=1e-5)
        grad = jax.jit(jax.grad(lambda *a: (splash(*a) ** 2).sum()))(*args)
        np.testing.assert_allclose(grad, ref_grad, atol=1e-4)
