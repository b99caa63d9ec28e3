"""``fsdp`` shards a layer's matrices inside the layer, and the train step
gathers one layer a layer (``GPT2Model.param_pspecs``, ``backbone``).

For the v5e, without a chip (the ``_aot_v5e.py`` route): ``make_train_step``'s
step compiled for the 2x2 host at GPT-2 XL widths, ``fsdp=4``, per-chip batch 2
-- the four-chip cell's program -- and its optimised HLO held to what was read
when the gather was stated: no loop body gathers a stack, every weight gather
in a loop is one layer in the compute dtype, the state stays sharded four ways;
and the one-chip cell's program holds no collective.  On the 8-device CPU mesh:
the sharded step is the one-device step, whatever the mesh.  (With ``fsdp`` on
the stacked dim both loops gathered the whole stack every iteration: 283 GB a
step, PERF.md section 6, PR 46.)"""

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import _aot_v5e  # noqa: E402
from ray_tpu.models.gpt2 import GPT2Config, GPT2Model  # noqa: E402
from ray_tpu.models.lm_train import make_train_step, synthetic_batch  # noqa: E402
from ray_tpu.parallel.mesh import MeshConfig, make_mesh  # noqa: E402

# ----------------------------------------- compiled for the v5e, without a chip

XL = dict(vocab_size=50257, n_layer=48, n_head=25, n_embd=1600, block_size=1024)
SMALL = dict(vocab_size=50257, n_layer=12, n_head=12, n_embd=768, block_size=1024)
COLLECTIVE = r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"


@pytest.fixture(scope="module")
def v5e():
    devices = _aot_v5e.topology_devices()
    if isinstance(devices, str):
        pytest.skip(devices)
    return devices


def _compile_step(widths, devices, per_chip_batch):
    """The train step over ``fsdp=len(devices)``, compiled from shapes alone,
    with the chip's attention kernel (off the chip "auto" takes the einsum)."""
    cfg = GPT2Config(**widths, attention_impl="splash")
    mesh = make_mesh(MeshConfig(fsdp=len(devices)), devices)
    b = make_train_step(GPT2Model(cfg), mesh)
    params, opt_state = jax.eval_shape(b.init, jax.random.PRNGKey(0))

    def placed(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings)

    tokens = jax.ShapeDtypeStruct((per_chip_batch * len(devices), cfg.block_size), jnp.int32, sharding=b.batch_sharding)
    compiled = b.step.lower(placed(params, b.param_shardings), placed(opt_state, b.opt_shardings), tokens, tokens).compile()
    return cfg, compiled.as_text(), compiled.memory_analysis()


@pytest.fixture(scope="module")
def xl_step(v5e):
    return _compile_step(XL, v5e[:4], 2)


def _loop_gathers(hlo):
    """[(dtype, dims)] of every all-gather a ``while`` body runs, the ones
    the compiler fused beside the matmul that reads them included."""
    comps, name = {}, None
    for ln in hlo.splitlines():
        if name is None and ln[:1] in "%E" and ln.endswith("{"):
            name = ln.split()[1 if ln.startswith("ENTRY") else 0].lstrip("%")
            comps[name] = []
        elif ln == "}":
            name = None
        elif name is not None:
            comps[name].append(ln)
    todo = re.findall(r"\bbody=%?([\w.\-]+)", hlo)
    assert todo, "the step has no loop: is the layer scan still there?"
    seen, found = set(), []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for ln in comps[comp]:
            todo += re.findall(r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)", ln)
            m = re.search(r" = (\w+)\[([\d,]*)\]\S* all-gather(?:-start)?\(", ln)
            if m:
                found.append((m.group(1), tuple(int(d) for d in m.group(2).split(",") if d)))
    return found


def test_no_loop_body_gathers_a_stack(xl_step):
    cfg, hlo, _ = xl_step
    gathers = _loop_gathers(hlo)
    assert gathers, "fsdp=4 and no gather in the layer loops: are the weights sharded at all?"
    assert not [g for g in gathers if g[1][0] == cfg.n_layer], gathers


def test_loop_gathers_are_one_layer_in_compute_dtype(xl_step):
    cfg, hlo, _ = xl_step
    E = cfg.n_embd
    layer = {(E, 3 * E), (E, E), (E, 4 * E), (4 * E, E)}
    big = [(dt, dims) for dt, dims in _loop_gathers(hlo) if np.prod(dims) * 2 >= _aot_v5e.BIG]
    assert {tuple(d for d in dims if d != 1) for _, dims in big} == layer, big  # all four, and nothing but them
    assert {dt for dt, _ in big} == {"bf16"}, big


def test_state_stays_sharded_and_the_step_fits(xl_step):
    _, _, ma = xl_step
    # float32 parameters and both moments, a quarter a chip: 4.951 GB before the move
    assert abs(ma.argument_size_in_bytes - 4.95e9) <= 0.01 * 4.95e9, ma.argument_size_in_bytes
    # 7.51 GB of temporaries (11.55 with the whole-stack gathers): room under the chip's 15.75
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes <= 13e9, ma


def test_one_chip_program_holds_no_collective(v5e):
    """The one-chip cell's step: the gather is not engaged, the kernels are."""
    _, hlo, _ = _compile_step(SMALL, v5e[:1], 18)
    assert not re.findall(r" (?:%s)(?:-start)?\(" % COLLECTIVE, hlo)
    assert hlo.count("tpu_custom_call") == 2  # splash forward, fused backward: as before


# ----------------------------------------------- on the CPU: the numbers agree


def _two_steps(cfg, mesh_config):
    model = GPT2Model(cfg)
    mesh = make_mesh(mesh_config, jax.devices()[: mesh_config.total_devices()])
    b = make_train_step(model, mesh, learning_rate=1e-3)
    tokens, targets = synthetic_batch(jax.random.PRNGKey(1), 8, cfg.block_size, cfg.vocab_size)
    tokens, targets = jax.device_put(tokens, b.batch_sharding), jax.device_put(targets, b.batch_sharding)
    p, o = b.init(jax.random.PRNGKey(0))
    metrics = []
    for _ in range(2):
        p, o, m = b.step(p, o, tokens, targets)
        metrics.append((float(m["loss"]), float(m["grad_norm"])))
    return metrics, jax.tree.map(np.asarray, p), b


_ONE = {}


def _one_device(cfg):
    if cfg not in _ONE:
        _ONE[cfg] = _two_steps(cfg, MeshConfig())[:2]
    return _ONE[cfg]


@pytest.mark.parametrize(
    "mesh_config",
    [MeshConfig(fsdp=4), MeshConfig(dp=2, fsdp=2, tp=2), MeshConfig(fsdp=4, keep_unit_axes=False)],
    ids=["fsdp4", "dp2-fsdp2-tp2", "fsdp4-bare"],
)
def test_sharded_step_is_the_one_device_step(mesh_config):
    cfg = GPT2Config.tiny(compute_dtype=jnp.float32, n_layer=3)
    metrics, params, b = _two_steps(cfg, mesh_config)
    want_metrics, want_params = _one_device(cfg)
    np.testing.assert_allclose(metrics, want_metrics, rtol=2e-6)
    # Adam's first steps are +-lr whatever the gradient's size: a sum in another order moves a weight by lr * 1e-2
    jax.tree.map(lambda a, w: np.testing.assert_allclose(a, w, atol=2e-5), params, want_params)
    specs = jax.tree.map(lambda s: s.spec, b.param_shardings["layers"])
    assert specs["qkv_w"][:2] == (None, "fsdp") and specs["mlp_out_w"][2] == "fsdp", specs
    assert all(s[0] is None for s in specs.values()), specs  # the scanned dim is whole on every device
    # ZeRO-1: the moments of a matrix lie as it does, those of a replicated vector are sharded all the same
    moments = jax.tree.map(lambda s: s.spec, b.opt_shardings[1][0].mu["layers"])
    assert moments["qkv_w"] == specs["qkv_w"] and "fsdp" in moments["ln1_scale"], moments


def test_rows_that_do_not_divide_replicate():
    cfg = GPT2Config.tiny(compute_dtype=jnp.float32, n_embd=66)  # 66 = 4 * 16 + 2
    metrics, params, b = _two_steps(cfg, MeshConfig(fsdp=4))
    assert not [s for s in jax.tree.leaves(b.param_shardings["layers"]) if "fsdp" in s.spec]
    want_metrics, want_params = _one_device(cfg)
    np.testing.assert_allclose(metrics, want_metrics, rtol=2e-6)
    jax.tree.map(lambda a, w: np.testing.assert_allclose(a, w, atol=2e-5), params, want_params)


def test_moe_layer_gathers_its_fsdp_shard_at_the_expert_boundary():
    """``_moe_mlp``'s shard_map names ``ep`` alone: the expert stacks, their
    rows split over fsdp, come to it whole."""
    cfg = GPT2Config.tiny(compute_dtype=jnp.float32, moe_experts=4, moe_capacity_factor=8.0)
    model = GPT2Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tokens, targets = synthetic_batch(jax.random.PRNGKey(1), 4, cfg.block_size, cfg.vocab_size)
    mesh = make_mesh(MeshConfig(fsdp=2, ep=2), jax.devices()[:4])
    specs = model.param_pspecs(mesh)
    assert specs["layers"]["expert_in"] == P(None, "ep", "fsdp", None), specs["layers"]
    placed = jax.tree.map(lambda a, s: jax.device_put(a, jax.sharding.NamedSharding(mesh, s)), params, specs)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: model.loss(p, tokens, targets, mesh)))(placed)
    want, want_grads = jax.value_and_grad(lambda p: model.loss(p, tokens, targets, None))(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    jax.tree.map(lambda g, w: np.testing.assert_allclose(g, w, atol=1e-5), grads, want_grads)


def test_pp_layouts_are_what_they_were():
    """Under pp the stacked dim is the stage dim and fsdp shards the batch alone."""
    model = GPT2Model(GPT2Config.tiny())
    layers = model.param_pspecs(make_mesh(MeshConfig(pp=2, dp=2, fsdp=2), jax.devices()[:8]))["layers"]
    assert layers == {
        "ln1_scale": P("pp", None), "ln1_bias": P("pp", None), "ln2_scale": P("pp", None), "ln2_bias": P("pp", None),
        "qkv_w": P("pp", None, "tp"), "qkv_b": P("pp", "tp"), "proj_w": P("pp", "tp", None), "proj_b": P("pp", None),
        "mlp_in_w": P("pp", None, "tp"), "mlp_in_b": P("pp", "tp"), "mlp_out_w": P("pp", "tp", None), "mlp_out_b": P("pp", None),
    }
