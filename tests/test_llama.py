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


def _llm(block, dtype):
    """A two-layer model of one of BLOCKS behind ``ShardedLLM``."""
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import ShardedLLM

    cfg = LlamaConfig(
        vocab_size=250, dim=64, n_layers=2, n_heads=4, max_seq_len=1024,
        compute_dtype=dtype, **BLOCKS[block],
    )
    return ShardedLLM(cfg, tp=1, init="random")


def _prompt(n):
    return [(7 * i + 3) % 250 for i in range(n)]


# What the paged programs' walk over context blocks must get right (a block
# is 256 positions, or the table where that is shorter): each fleet is
# (prompts, new tokens, page size, prefill chunk, pages in a slot's table).
# A request's last attended position is len(prompt) + new - 2.
FLEETS = {
    # under, at and over a chunk; over a page; the table is one short block
    "one-short-block-page4": ([[5, 7, 9], [3], list(range(1, 12))], 8, 4, 4, None),
    "one-short-block-page16": ([[5, 7, 9], [3], list(range(1, 12))], 8, 16, 4, None),
    # contexts that end inside block 0, on its last position and one past it,
    # in a table of 40 pages: two and a half blocks
    "block-edges-ragged-table": ([_prompt(100), _prompt(253), _prompt(254)], 4, 16, 64, 40),
    # the longest live context is one block of three: the walk stops early
    "one-block-of-three": ([_prompt(3), _prompt(200), _prompt(17)], 4, 16, 64, 48),
    # the longest context fills its table of two blocks; the others stay in the first
    "fills-the-table": ([_prompt(508), _prompt(9), _prompt(250)], 4, 16, 128, 32),
}


@pytest.mark.parametrize("fleet", list(FLEETS))
@pytest.mark.parametrize("block", list(BLOCKS))
def test_paged_programs_match_the_plain_forward(block, fleet):
    """The engine's two programs (chunked prefill into a paged pool, then
    whole-fleet decode steps) give, token for token in float32, what the
    plain causal forward gives for each prompt alone -- however far the
    fleet's longest context makes the walk over blocks go, and with one
    compilation a program (``paged_greedy`` asserts it)."""
    import jax.numpy as jnp

    from _greedy import greedy_reference, paged_greedy

    prompts, n_new, page_size, chunk, pages_per_slot = FLEETS[fleet]
    llm = _llm(block, jnp.float32)
    outs = paged_greedy(
        llm, prompts, n_new, page_size=page_size, chunk=chunk, pages_per_slot=pages_per_slot
    )
    for prompt, out in zip(prompts, outs):
        assert out == greedy_reference(llm.model, llm.params, prompt, n_new)
        assert all(0 <= t < llm.cfg.vocab_size for t in out)


def _decode_fixture(block, dtype):
    """A model of one of BLOCKS, its decode program for three slots with
    tables of 32 pages of 16 (two blocks), a pool of random K/V (as if
    earlier turns had written it) and tables that interleave the slots'
    pages: (llm, decode, fresh_pool, tables)."""
    import jax
    import jax.numpy as jnp

    from _greedy import interleaved_tables

    llm = _llm(block, dtype)
    programs = llm.engine_programs(num_pages=3 * 32, page_size=16)
    tables = interleaved_tables(3, 32)

    def fresh_pool():
        kp, vp, *rest = programs["init"]()
        keys = jax.random.split(jax.random.PRNGKey(7), 2)
        return (
            jax.random.normal(keys[0], kp.shape, jnp.float32).astype(kp.dtype),
            jax.random.normal(keys[1], vp.shape, jnp.float32).astype(vp.dtype),
            *rest,
        )

    return llm, programs["decode"], fresh_pool, tables


@pytest.mark.parametrize("block", list(BLOCKS))
def test_a_decode_row_is_bit_identical_whatever_the_other_rows_lengths(block):
    """Slot 1 sits in block 0 of a two-block table.  Its token and the K/V
    it writes (layer 1's depend on layer 0's attention) carry the same bits
    whether the other slots keep the walk to one block or drive it over the
    whole table, in the chip's precision."""
    import jax.numpy as jnp

    llm, decode, fresh_pool, tables = _decode_fixture(block, jnp.bfloat16)
    tokens = np.array([11, 42, 7], np.int32)

    def run(others):
        positions = np.array([others[0], 40, others[1]], np.int32)
        nxt, pool = decode(llm.params, fresh_pool(), tables, tokens, positions, np.ones(3, bool))
        mine = tables[1][40 // 16]
        return [int(np.asarray(nxt)[1])] + [np.asarray(a.astype(jnp.float32))[:, mine, 40 % 16] for a in pool[:2]]

    near, far = run((10, 100)), run((511, 300))
    assert near[0] == far[0]
    for a, b in zip(near[1:], far[1:]):
        assert np.array_equal(a, b) and np.abs(a).max() > 0
    assert decode._cache_size() == 1


@pytest.mark.parametrize("block", list(BLOCKS))
def test_a_decode_step_with_no_active_row_is_finite_and_writes_nothing(block):
    import jax.numpy as jnp

    llm, decode, fresh_pool, tables = _decode_fixture(block, jnp.float32)
    before = fresh_pool()
    idle = np.zeros(3, bool)
    nxt, pool = decode(llm.params, fresh_pool(), tables, np.zeros(3, np.int32), np.array([0, 300, 511], np.int32), idle)
    assert all(0 <= int(t) < 250 for t in np.asarray(nxt))
    for a, b in zip(before, pool):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the attention itself, whose result the program's argmax would hide
    q = jnp.ones((3, 1, 4, 16), jnp.float32)
    out = llm.model._paged_attend(
        q, pool[0], pool[1], 0, jnp.asarray(tables).reshape(3, 2, 16), jnp.zeros((3, 1), jnp.int32), jnp.asarray(idle)[:, None], 2
    )
    assert out.shape == (3, 1, 64) and bool(jnp.isfinite(out).all())


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its equations' params."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for item in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_the_decode_program_walks_blocks_and_never_builds_the_whole_context():
    """Lowering of the grouped-query decode step over a four-block table:
    one ``while`` a layer, and no value as large as a slot-by-table context
    [S, T, KV, D] -- what the whole-table gather made, and a quarter of the
    K/V repeated to H heads."""
    import functools

    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig(
        vocab_size=250, dim=64, n_layers=2, n_heads=4, max_seq_len=1024,
        compute_dtype=jnp.float32, **BLOCKS["grouped-query"],
    )
    model = LlamaModel(cfg)  # shapes only: no weights are made
    S, MP, PS = 2, 64, 16
    KV, D = cfg.n_kv_heads, cfg.head_dim
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pool = jax.eval_shape(lambda: model.init_pages(S * MP, PS))
    closed = jax.make_jaxpr(functools.partial(model.decode_step_paged, page_size=PS))(
        params, pool, jnp.zeros((S, MP), jnp.int32), jnp.zeros(S, jnp.int32), jnp.zeros(S, jnp.int32), jnp.ones(S, bool)
    )
    eqns = list(_equations(closed.jaxpr))
    assert sum(e.primitive.name == "while" for e in eqns) == cfg.n_layers
    context = S * MP * PS * KV * D
    for eqn in eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            if shape == pool[0].shape:  # the pool itself, through a layer's write or a loop
                continue
            assert int(np.prod(shape)) < context, (eqn.primitive.name, shape)
    gathers = [e for e in eqns if e.primitive.name == "gather" and e.outvars[0].aval.shape[-2:] == (KV, D)]
    assert gathers and all(int(np.prod(e.outvars[0].aval.shape)) == S * 256 * KV * D for e in gathers)
