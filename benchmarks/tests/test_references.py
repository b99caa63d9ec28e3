"""Each plain reference against the system at a tiny size on the CPU."""

import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import run as bench_run

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny(name):
    return bench_run.merge_tiny(bench_run.load_json(os.path.join(HERE, "configs", f"{name}.json")))


def test_gpt2_reference_matches_the_system_in_float32():
    from benchmarks.drivers import train as train_driver
    from ray_tpu.models.gpt2 import GPT2Config, GPT2Model
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh

    cfg = _tiny("gpt2-124m")
    gcfg = GPT2Config(vocab_size=cfg["vocab_size"], n_layer=cfg["n_layer"], n_head=cfg["n_head"], n_embd=cfg["n_embd"],
                      block_size=cfg["n_positions"], compute_dtype=jnp.float32, param_dtype=jnp.float32)
    model = GPT2Model(gcfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh(MeshConfig(), jax.devices()[:1])
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg["vocab_size"], (2, 65)).astype(np.int32)
    out = train_driver.reference_check(model, params, mesh, cfg, (toks[:, :-1], toks[:, 1:]), 2)
    # same arithmetic in float32: only the order of the reductions differs
    assert out["loss_rel_err"] < 1e-5 and out["grad_norm_rel_err"] < 1e-4
    # and the comparison can fail: weights rounded to bf16 are another model
    rounded = train_driver.reference_check(model, params, mesh, cfg, (toks[:, :-1], toks[:, 1:]), 2, cast=jnp.bfloat16)
    assert rounded["grad_norm_rel_err"] > 10 * out["grad_norm_rel_err"]


def test_llama_reference_matches_prefill_then_decode_through_the_paged_cache():
    from benchmarks.drivers import serve as serve_driver

    cfg = _tiny("mistral-7b-l16")
    cfg["torch_dtype"] = "float32"
    out = serve_driver._reference_check_in_worker(cfg, 3)
    assert out["prompt_len"] > cfg["engine"]["prefill_chunk"]  # more than one chunk, the last one padded
    assert out["k_rel_err"] < 1e-4 and out["v_rel_err"] < 1e-4
    assert out["first_logit_gap"] < 1e-4 and out["second_logit_gap"] < 1e-4
    assert out["ok"]
