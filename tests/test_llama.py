"""Llama model tests: forward/loss, sharded train step, and the engine's
paged programs against the plain forward."""

import numpy as np
import pytest


def test_llama_loss_near_uniform():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig.tiny(compute_dtype=jnp.float32)
    model = LlamaModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab_size)
    loss = float(model.loss(params, tokens[:, :-1], tokens[:, 1:]))
    assert abs(loss - np.log(cfg.vocab_size)) < 0.5


def test_llama_sharded_train_step():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, LlamaModel
    from ray_tpu.models.lm_train import make_train_step, synthetic_batch
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg = LlamaConfig.tiny(compute_dtype=jnp.float32)
    model = LlamaModel(cfg)
    mesh = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    bundle = make_train_step(model, mesh, learning_rate=1e-2)
    params, opt_state = bundle.init(jax.random.PRNGKey(0))
    tok, tgt = synthetic_batch(jax.random.PRNGKey(1), 8, 32, cfg.vocab_size)
    first = None
    for _ in range(20):
        params, opt_state, m = bundle.step(params, opt_state, tok, tgt)
        if first is None:
            first = float(m["loss"])
    assert float(m["loss"]) < first - 1.0


# The block's three kinds at a tiny size: grouped-query dense (Mistral's
# shape), one KV head a query head, and routed experts with QK-norm (OLMoE's).
# vocab_size 250 leaves six padded ids that must never be sampled.
BLOCKS = {
    "grouped-query": dict(n_kv_heads=2, hidden_dim=128),
    "kv-equals-h": dict(n_kv_heads=4, hidden_dim=128),
    "experts-qk-norm": dict(n_kv_heads=4, hidden_dim=32, n_experts=8, n_experts_per_tok=2, qk_norm=True),
}


@pytest.mark.parametrize("page_size", [4, 16])
@pytest.mark.parametrize("block", list(BLOCKS))
def test_paged_programs_match_the_plain_forward(block, page_size):
    """The engine's two programs (chunked prefill into a paged pool, then
    whole-fleet decode steps) give, token for token in float32, what the
    plain causal forward gives for each prompt alone."""
    import jax.numpy as jnp

    from _greedy import greedy_reference, paged_greedy
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import ShardedLLM

    cfg = LlamaConfig(
        vocab_size=250, dim=64, n_layers=2, n_heads=4, max_seq_len=64,
        compute_dtype=jnp.float32, **BLOCKS[block],
    )
    llm = ShardedLLM(cfg, tp=1, init="random")
    prompts = [[5, 7, 9], [3], list(range(1, 12))]  # under, at and over a chunk; over a page
    outs = paged_greedy(llm, prompts, 8, page_size=page_size, chunk=4)
    for prompt, out in zip(prompts, outs):
        assert out == greedy_reference(llm.model, llm.params, prompt, 8)
        assert all(0 <= t < cfg.vocab_size for t in out)
