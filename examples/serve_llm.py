"""LLM serving: the tp-sharded model behind the continuous-batching
engine — buffered answers and a token stream — plus a streaming generator
endpoint.  Tiny config here; a larger `LlamaConfig` on one chip, or with
tp over a mesh, uses the same code path."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from examples._common import setup_local_env

setup_local_env()

import jax.numpy as jnp

import ray_tpu
from ray_tpu import serve


def main():
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import engine_llm_deployment

    ray_tpu.init(num_cpus=4)

    cfg = LlamaConfig(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128,
        vocab_size=256, compute_dtype=jnp.float32,
    )
    dep = engine_llm_deployment(cfg, max_seq_len=64, new_tokens=8, num_slots=4,
                                page_size=8, prefill_chunk=8, num_tpus=0, tp=1)
    handle = serve.run(dep.bind())
    outs = ray_tpu.get([handle.remote(i) for i in range(4)], timeout=300)
    print("buffered generations:", outs[0])
    for frame in handle.stream_tokens({"prompt": [1, 2, 3], "max_new_tokens": 6}):
        print("streamed tokens:", frame)

    # streaming: a generator deployment yields tokens as produced
    @serve.deployment(name="streamer")
    def stream_tokens(prompt):
        for i in range(5):
            yield {"token": f"tok{i}", "prompt": prompt}

    shandle = serve.run(stream_tokens.bind())
    for chunk in shandle.stream("hello"):
        print("streamed:", chunk)

    serve.shutdown()
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
