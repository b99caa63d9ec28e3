"""The serving weights are read where they lie (``LlamaModel._qkv``), and a
weight swap lands where the weights were (``ShardedLLM.place``).

For the v5e, without a chip: both engine programs of each serving
configuration compiled at its published widths (``_aot_v5e.py``) and held to
what was read when ``_qkv`` got its barrier -- no weight-sized copy in either
Mistral program, OLMoE's and Qwen3-Next's programs as they were, Jamba's with
no weight-sized copy and no expanded scan state -- so that a compiler update
that changes its mind fails here first.  On the CPU:
the barrier changes no token, and every way weights arrive is placed with the
shardings ``llm.params`` has, so the two compiled programs go on serving."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import _aot_v5e  # noqa: E402
from _greedy import greedy_reference, paged_greedy  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.serve.llm import ShardedLLM  # noqa: E402

PROMPTS = [[5, 7, 9], [3], list(range(1, 12)), [4, 4]]


def _dense():
    return LlamaConfig.tiny(compute_dtype=jnp.float32)


# ----------------------------------------- compiled for the v5e, without a chip

_DEVICES = None


def _v5e():
    global _DEVICES
    if _DEVICES is None:
        _DEVICES = _aot_v5e.topology_devices()
    if isinstance(_DEVICES, str):
        pytest.skip(_DEVICES)
    return _DEVICES


# What each program may still copy of its weights a call, in bytes, as read
# when this was written (libtpu 0.0.34, jax 0.9.0).  Qwen3-Next's decode step
# transposes the gated-attention layer's ``full.wq`` [2048, 8192] (33.5 MB a
# full layer, ~0.1 ms of a 14 ms step at the cell's two: PERF.md section 7).
KNOWN_COPIES = {
    ("mistral-7b-l16", "decode"): 0, ("mistral-7b-l16", "prefill"): 0,
    ("olmoe-1b-7b-l8", "decode"): 0, ("olmoe-1b-7b-l8", "prefill"): 0,
    ("qwen3-next-80b-a3b-l8-ep4", "decode"): 33_554_432, ("qwen3-next-80b-a3b-l8-ep4", "prefill"): 0,
}


# layers: Mistral 3 (at 1-2 the compiler parks whole layers in faster memory
# and the byte count no longer tells the copies), OLMoE 2, Qwen3-Next one period
@pytest.mark.parametrize("name, layers", [("mistral-7b-l16", 3), ("olmoe-1b-7b-l8", 2), ("qwen3-next-80b-a3b-l8-ep4", 4)])
def test_v5e_programs_copy_no_weight(name, layers):
    devices = _v5e()
    lcfg, engine = _aot_v5e.load_config(name, layers)
    compiled = _aot_v5e.compile_programs(lcfg, engine, devices)
    for prog in _aot_v5e.PROGRAMS:
        hlo, _ = compiled[prog]
        # no synchronous operation but a matmul reads a parameter and writes a weight-sized result
        copies = _aot_v5e.weight_relayouts(hlo)
        assert sum(b for _, _, b in copies) <= KNOWN_COPIES[name, prog], (name, prog, copies)
    if name != "mistral-7b-l16":
        return
    # the block without QK-norm: the barrier in _qkv is what does it, and still does
    with _aot_v5e.without_barrier():
        before = _aot_v5e.compile_programs(lcfg, engine, devices)
    for prog in _aot_v5e.PROGRAMS:
        (hlo, nbytes), (hlo_before, nbytes_before) = compiled[prog], before[prog]
        assert _aot_v5e.device_ops_named(hlo, "slice_bitcast_fusion") == [], prog
        assert len(_aot_v5e.device_ops_named(hlo_before, "slice_bitcast_fusion")) == 3, prog
        assert nbytes_before - nbytes >= 0.05e9 * layers, (prog, nbytes_before, nbytes)


def test_v5e_jamba_programs_copy_no_weight_and_hold_no_expanded_state(monkeypatch):
    """Jamba2-3B's two programs at the published widths over the reference
    check's four layers (Mamba Mamba attention Mamba), with the scan's Pallas
    kernel (the ahead-of-time compile takes the Mosaic call): no weight-sized
    copy -- the tied head is contracted against the embedding where it lies,
    there is no [E, V] twin --, the pool aliased into the result, and no array
    of rows x d_inner x d_state elements or more but the weights and the pool's
    own members: the scan's expanded state never reaches HBM."""
    import numpy as np

    from ray_tpu.ops import selective_scan

    devices = _v5e()
    monkeypatch.setattr(selective_scan, "_on_tpu", lambda: True)  # the chip's form; this process sees the CPU
    lcfg, engine = _aot_v5e.load_config("jamba2-3b", 4)
    assert lcfg.layer_kinds == ("mamba", "mamba", "attn", "mamba") and (lcfg.dim, lcfg.d_inner, lcfg.d_state) == (2560, 5120, 16)
    compiled = _aot_v5e.compile_programs(lcfg, engine, devices)
    model = lcfg.build_model()
    slots = int(engine["num_slots"])
    pool = jax.eval_shape(lambda: model.init_pages(slots * int(engine["max_seq_len"]) // int(engine["page_size"]), int(engine["page_size"]), slots))
    weights = jax.tree.leaves(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    allowed = {tuple(a.shape) for a in pool}
    for a, role in zip(pool, model.pool_roles()):
        if role == "pages":  # one attending layer's pages, as the walk views them (its one KV head squeezed)
            one = tuple(a.shape[1:])
            allowed |= {one, (1, *one), tuple(d for d in one if d != 1), (1, *(d for d in one if d != 1))}
    for w in weights:  # a stack, one layer of it, and that layer as a stack of one
        allowed |= {tuple(w.shape), tuple(w.shape[1:]), (1, *w.shape[1:])}
    for prog, rows in (("decode", slots), ("prefill", int(engine["prefill_chunk"]))):
        hlo, _ = compiled[prog]
        assert _aot_v5e.weight_relayouts(hlo) == [], prog
        assert hlo.count('custom_call_target="tpu_custom_call"') == 3, prog  # one scan a Mamba layer
        shapes = _aot_v5e.array_shapes(hlo)
        assert ("bf16", (lcfg.dim, lcfg.padded_vocab)) not in shapes and ("bf16", (1, lcfg.dim, lcfg.padded_vocab)) not in shapes, prog
        expanded = rows * lcfg.d_inner * lcfg.d_state
        big = {(dt, dims) for dt, dims in shapes if int(np.prod(dims)) >= expanded and dims not in allowed}
        assert not big, (prog, sorted(big))


def test_v5e_moonlight_programs_keep_the_cache_latent():
    """Moonlight-16B-A3B's two programs at the published widths over the
    reference check's three layers (the dense one, two of experts) and the
    cell's own pool: no weight-sized copy; the pool in ONE layout, row-major,
    from the entry to the result (at 576 wide the device's own choice puts the
    page axis minor-most and both programs copy the whole pool to row-major
    and back: ``DeepseekV3Config.cache_row_dim``); and no array as large as
    one walked block's per-head keys but the weights, the pool and the
    logits: the context's K and V are never formed."""
    import numpy as np

    devices = _v5e()
    lcfg, engine = _aot_v5e.load_config("moonlight-16b-a3b-l8", 3)
    assert (lcfg.latent_dim, lcfg.cache_row_dim, lcfg.n_heads, lcfg.first_k_dense, lcfg.n_experts) == (576, 640, 16, 1, 64)
    compiled = _aot_v5e.compile_programs(lcfg, engine, devices)
    model = lcfg.build_model()
    slots, chunk = int(engine["num_slots"]), int(engine["prefill_chunk"])
    pool = jax.eval_shape(lambda: model.init_pages(int(engine["num_pages"]), int(engine["page_size"]), slots))
    assert [tuple(a.shape) for a in pool] == [(3, 18432, 16, 640), (64,)]
    allowed = {tuple(pool[0].shape)}
    for w in jax.tree.leaves(jax.eval_shape(model.init, jax.random.PRNGKey(0))):  # a stack, one layer of it, and that layer as a stack of one
        allowed |= {tuple(w.shape), tuple(w.shape[1:]), (1, *w.shape[1:])}
    pool_text = "bf16[3,18432,16,640]"
    for prog, rows in (("decode", slots), ("prefill", chunk)):
        hlo, _ = compiled[prog]
        assert _aot_v5e.weight_relayouts(hlo) == [], prog
        layouts = {hlo[i + len(pool_text) : hlo.index("}", i) + 1] for i in range(len(hlo)) if hlo.startswith(pool_text + "{", i)}
        assert layouts == {"{3,2,1,0:T(8,128)(2,1)}"}, (prog, layouts)
        block_keys = slots * 256 * lcfg.n_heads * lcfg.qk_nope_head_dim  # one walked block of the decode step, expanded
        logits = {(rows, lcfg.padded_vocab), (1, rows, lcfg.padded_vocab)}
        big = {(dt, dims) for dt, dims in _aot_v5e.array_shapes(hlo) if int(np.prod(dims)) >= block_keys and dims not in allowed | logits}
        assert not big, (prog, sorted(big))


def test_v5e_lfm2_programs_read_their_weights_and_the_pool_where_they_lie():
    """LFM2-24B-A2B's two programs at the published widths over the reference
    check's four layers (both dense conv layers, an attending and a conv layer
    with experts: all four stacks) and the cell's own pool: no weight-sized
    copy -- neither ``wq``/``wk``/``wv``, whose per-head norm FOLLOWS the split
    into heads (``_qkv``'s barrier, restated in ``Lfm2MoeModel._attn``), nor
    the conv mixer's ``w_in`` and ``w_out`` --; the K/V pages in ONE layout,
    row-major, from the entry to the result (as [.., 16, 8, 64] the device's
    own choice puts the page axis minor-most and both programs copy the pool
    to row-major and back, 4 GB of temporaries: ``Lfm2MoeModel.init_pages``);
    nothing rematerialised; temporaries as the configuration's ``engine_note``
    states them."""
    devices = _v5e()
    lcfg, engine = _aot_v5e.load_config("lfm2-24b-a2b-l10", 4)
    assert (lcfg.layer_kinds, lcfg.n_dense_layers, lcfg.n_experts, lcfg.head_dim) == (("conv", "conv", "attn", "conv"), 2, 64, 64)
    compiled = _aot_v5e.compile_programs(lcfg, engine, devices, with_memory=True)
    model = lcfg.build_model()
    pool = jax.eval_shape(lambda: model.init_pages(int(engine["num_pages"]), int(engine["page_size"]), int(engine["num_slots"])))
    assert [tuple(a.shape) for a in pool] == [(1, 30720, 16, 512), (1, 30720, 16, 512), (64,), (3, 96, 2, 2048)]
    pool_text = "bf16[1,30720,16,512]"
    for prog in _aot_v5e.PROGRAMS:
        hlo, _, memory = compiled[prog]
        assert _aot_v5e.weight_relayouts(hlo) == [], prog
        layouts = {hlo[i + len(pool_text) : hlo.index("}", i) + 1] for i in range(len(hlo)) if hlo.startswith(pool_text + "{", i)}
        assert layouts == {"{3,2,1,0:T(8,128)(2,1)}"}, (prog, layouts)
        assert ".remat" not in hlo, prog
        assert memory.temp_size_in_bytes < 32 << 20, (prog, memory.temp_size_in_bytes)  # 10 MB and 9 MB at all ten layers
        assert memory.alias_size_in_bytes >= sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in pool) - 4096, prog  # the pool is updated in place


def test_v5e_qwen3_next_decode_step_reads_one_expert_a_visit():
    """The cell's own decode program (8 layers, 16 slots, 128 of 512 experts
    held): the routed layer's touched form (``parallel/moe.py``).  Nothing in
    it reads, converts or copies a layer's whole expert stack -- no array
    with a [128, 2048, 512] in it but the parameters themselves, which the
    eight loops carry through untouched -- and all that is cut out of a stack
    is ONE expert, three times a layer, inside the loop that visits the
    touched experts.  The chunk program (256 rows) keeps the masked
    contraction over a layer's whole stacks."""
    import re

    devices = _v5e()
    lcfg, engine = _aot_v5e.load_config("qwen3-next-80b-a3b-l8-ep4", 8)
    held, E, H = lcfg.n_experts, lcfg.dim, lcfg.hidden_dim
    assert (int(engine["num_slots"]), held, lcfg.n_routed_experts, lcfg.n_experts_per_tok, E, H) == (16, 128, 512, 10, 2048, 512)
    compiled = _aot_v5e.compile_programs(lcfg, engine, devices)
    a_layers_stack = lambda shapes: {dims for _, dims in shapes if tuple(d for d in dims if d != 1) in ((held, E, H), (held, H, E))}  # noqa: E731
    one_expert = re.compile(r"= bf16\[1,1,(?:%d,%d|%d,%d)\]\S* dynamic-slice\(" % (E, H, H, E))
    decode, chunk = compiled["decode"][0], compiled["prefill"][0]
    assert a_layers_stack(_aot_v5e.array_shapes(decode)) == set()
    assert len(one_expert.findall(decode)) == 3 * lcfg.n_layers
    assert decode.count(" while(") == lcfg.n_layers + lcfg.layer_kinds.count("full")  # a visit loop a layer, a walk a full layer
    assert sum(b for _, _, b in _aot_v5e.weight_relayouts(decode)) <= 2 * KNOWN_COPIES["qwen3-next-80b-a3b-l8-ep4", "decode"]  # two full layers' wq
    assert a_layers_stack(_aot_v5e.array_shapes(chunk)) and not one_expert.findall(chunk)
    assert chunk.count(" while(") == lcfg.n_layers  # six scans, two walks
    assert _aot_v5e.weight_relayouts(chunk) == []


def test_the_relayout_reader_sees_a_copy_where_there_is_one():
    hlo = """HloModule m
%fused_computation.1 (p: bf16[16,4096,1024]) -> bf16[1024,4096] {
  %p = bf16[16,4096,1024]{2,1,0} parameter(0)
  ROOT %t = bf16[1024,4096]{0,1} bitcast(%p)
}
%fused_computation.2 (p: bf16[16,4096,1024], x: bf16[16,4096]) -> bf16[16,1024] {
  %p = bf16[16,4096,1024]{2,1,0} parameter(0)
  %x = bf16[16,4096]{1,0} parameter(1)
  ROOT %c = bf16[16,1024]{1,0} convolution(%x, %p), dim_labels=bf_io->bf
}
ENTRY %main (params__layers____wk__.1: bf16[16,4096,1024], x.1: bf16[16,4096]) -> bf16[16,1024] {
  %params__layers____wk__.1 = bf16[16,4096,1024]{2,1,0} parameter(0)
  %x.1 = bf16[16,4096]{1,0} parameter(1)
  %bitcast.7 = bf16[16,4096,8,128]{3,2,1,0} bitcast(%params__layers____wk__.1)
  %slice_bitcast_fusion = bf16[1024,4096]{0,1} fusion(%bitcast.7), kind=kLoop, calls=%fused_computation.1
  %slice-start = bf16[1024,4096]{0,1:S(1)} slice-start(%params__layers____wk__.1), slice={[0:1]}
  ROOT %fusion.2 = bf16[16,1024]{1,0} fusion(%params__layers____wk__.1, %x.1), kind=kOutput, calls=%fused_computation.2
}
"""
    # the copy through a bitcast counts; the prefetch and the matmul do not
    assert _aot_v5e.weight_relayouts(hlo) == [("slice_bitcast_fusion", "bf16[1024,4096]", 8_388_608)]
    assert _aot_v5e.device_ops_named(hlo, "slice_bitcast_fusion") == ["slice_bitcast_fusion"]


# -------------------------------------------------------------- on the CPU


def test_the_barrier_changes_no_token_and_no_gradient():
    """``_qkv`` with its barrier against ``_qkv`` traced without it: the
    paged programs' tokens, the plain forward's logits and a loss's gradient."""
    cfg = _dense()
    llm = ShardedLLM(cfg, tp=1)
    toks = jnp.asarray([PROMPTS[2]])
    loss = jax.jit(jax.value_and_grad(lambda p: llm.model.loss(p, toks[:, :-1], toks[:, 1:])))
    got_tokens = paged_greedy(llm, PROMPTS[:2], 5, page_size=4, chunk=4)
    (got_loss, got_grad), got_logits = loss(llm.params), llm.model.apply(llm.params, toks)
    with _aot_v5e.without_barrier():
        plain = ShardedLLM(cfg, tp=1)
        loss = jax.jit(jax.value_and_grad(lambda p: plain.model.loss(p, toks[:, :-1], toks[:, 1:])))
        assert paged_greedy(plain, PROMPTS[:2], 5, page_size=4, chunk=4) == got_tokens
        (want_loss, want_grad), want_logits = loss(plain.params), plain.model.apply(plain.params, toks)
    np.testing.assert_array_equal(np.asarray(got_logits), np.asarray(want_logits))
    assert float(got_loss) == float(want_loss)
    for g, w in zip(jax.tree_util.tree_leaves(got_grad), jax.tree_util.tree_leaves(want_grad)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6, atol=1e-7)


def _placed_as_params(llm):
    return all(
        leaf.sharding.is_equivalent_to(sh, leaf.ndim) and leaf.committed
        for leaf, sh in zip(jax.tree_util.tree_leaves(llm.params), jax.tree_util.tree_leaves(llm.param_shardings))
    )


@pytest.mark.parametrize("init", ["random", "cheap", "dict", "abstract"])
def test_every_init_carries_the_replicas_shardings(init):
    cfg = _dense()
    if init == "dict":
        host = jax.tree.map(np.asarray, ShardedLLM(cfg, tp=1).params)
        llm = ShardedLLM(cfg, tp=2, init=host)
        assert paged_greedy(llm, PROMPTS[:2], 4, page_size=4, chunk=4) == paged_greedy(ShardedLLM(cfg, tp=1), PROMPTS[:2], 4, page_size=4, chunk=4)
    else:
        llm = ShardedLLM(cfg, tp=2, init=init)
    if init == "abstract":
        leaves = jax.tree_util.tree_leaves(llm.params)
        assert all(isinstance(a, jax.ShapeDtypeStruct) for a in leaves)
        assert [a.sharding for a in leaves] == jax.tree_util.tree_leaves(llm.param_shardings)
        shapes = jax.eval_shape(llm.model.init, jax.random.PRNGKey(0))
        assert [(a.shape, a.dtype) for a in leaves] == [(s.shape, s.dtype) for s in jax.tree_util.tree_leaves(shapes)]
    else:
        assert _placed_as_params(llm)


@pytest.mark.parametrize("form", ["tree", "flat"])
def test_update_weights_lands_where_params_were(form):
    """A hot swap from a host tree, and from a flat vector, is placed as
    ``llm.params`` was (tp=2: sharded, committed), so the two compiled
    programs go on serving (counts 1/1) and answer with the new weights."""
    from jax.flatten_util import ravel_pytree

    from ray_tpu.serve.engine import EngineConfig, InferenceEngine

    cfg = _dense()
    llm = ShardedLLM(cfg, tp=2, seed=0)
    new = jax.tree.map(np.asarray, ShardedLLM(cfg, tp=1, seed=1).params)  # host arrays
    want = greedy_reference(llm.model, new, PROMPTS[0], 5)
    eng = InferenceEngine(llm, EngineConfig(num_slots=2, page_size=4, max_seq_len=32, prefill_chunk=4), deployment="t")
    try:
        before = eng.submit(PROMPTS[0], 5).sink.result(timeout=120)
        eng.update_weights(ravel_pytree(new)[0] if form == "flat" else new)
        after = eng.submit(PROMPTS[0], 5).sink.result(timeout=120)
        st = eng.stats()
    finally:
        eng.shutdown()
    assert eng.weight_updates == 1 and after == want and before != after
    assert _placed_as_params(llm)
    assert (st["compile_prefill"], st["compile_decode"]) == (1, 1)
    with pytest.raises(ValueError):
        eng.update_weights()
