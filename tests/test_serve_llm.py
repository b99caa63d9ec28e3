"""ShardedLLM: a LlamaConfig model sharded over a tp mesh, decoding through
the engine's paged programs — toy scale on the CPU's virtual devices."""

import pytest
from _greedy import paged_greedy


def _tiny_cfg():
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig.tiny(compute_dtype=jnp.float32)


def test_sharded_llm_tp_equals_single_device():
    """The tp-sharded paged programs give the unsharded programs' tokens:
    one prefill chunk a prompt, then five decode steps over both slots."""
    from ray_tpu.serve.llm import ShardedLLM

    cfg = _tiny_cfg()
    prompts = [[5, 7, 9], [3, 2, 1]]
    t1 = paged_greedy(ShardedLLM(cfg, tp=1, init="random"), prompts, 6, page_size=4, chunk=4)
    t2 = paged_greedy(ShardedLLM(cfg, tp=2, init="random"), prompts, 6, page_size=4, chunk=4)
    assert [len(t) for t in t1] == [6, 6]
    assert t1 == t2


def test_sharded_llm_shard_stats_split_params():
    from ray_tpu.serve.llm import ShardedLLM

    eng = ShardedLLM(_tiny_cfg(), tp=2, init="random")
    st = eng.shard_stats()
    per = list(st["per_device_bytes"].values())
    assert len(per) == 2
    # every big matrix is tp-sharded; only the tiny norm scales replicate
    assert max(per) < st["total_bytes"] * 0.75


def test_sharded_llm_cheap_init_decodes():
    """The "cheap" per-shard fill decodes through the paged programs."""
    from ray_tpu.serve.llm import ShardedLLM

    cfg = _tiny_cfg()
    (toks,) = paged_greedy(ShardedLLM(cfg, tp=2, init="cheap"), [[1, 2, 3]], 4, page_size=4, chunk=4)
    assert len(toks) == 4
    assert all(0 <= t < cfg.vocab_size for t in toks)


def test_sharded_llm_rejects_bad_tp():
    from ray_tpu.serve.llm import ShardedLLM

    with pytest.raises(ValueError):
        ShardedLLM(_tiny_cfg(), tp=3, init="random")  # kv_heads=2 % 3
