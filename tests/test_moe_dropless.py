"""The dropless routed layer's two forms (parallel/moe.py dropless_moe_ffn):
the masked contraction over every held expert, and the form a decode step of
few rows over a wide router takes, which reads the weights of the held
experts its live rows chose and of no other.  One layer, one meaning: the
forms agree on every live row, and which one a call takes is a rule over
its shapes -- for a caller that hands the layer its whole stacks (``layer``),
which is what the touched form needs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.parallel import moe

T, E, H, X, K = 6, 32, 16, 32, 2  # 2 * 6 * 2 <= 32: the touched form


def _layer(seed=0, experts=X, rows=T):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    return f(rows, E), f(E, experts), f(experts, E, H) * 0.3, f(experts, E, H) * 0.3, f(experts, H, E) * 0.3, f(experts) * 0.5


def _masked(monkeypatch, *args, **kw):
    with monkeypatch.context() as m:
        m.setattr(moe, "reads_touched_experts_only", lambda *a: False)
        return moe.dropless_moe_ffn(*args, **kw)


def _stack(w, layer=1, layers=3):
    """A model's whole stack [L, ...] with ``w`` as layer ``layer`` and NaN in every other."""
    return jnp.stack([w if i == layer else jnp.full_like(w, jnp.nan) for i in range(layers)])


# ---------------------------------------------------------------- the rule


@pytest.mark.parametrize(
    "rows, top_k, experts, touched",
    [
        (16, 10, 512, True),  # Qwen3-Next's decode step
        (256, 10, 512, False),  # its chunk
        (32, 8, 64, False),  # OLMoE's decode step
        (64, 6, 64, False),  # Moonlight's
        (96, 4, 64, False),  # LFM2's
        (256, 8, 64, False),  # OLMoE's chunk
    ],
)
def test_the_form_is_a_rule_over_the_calls_shapes(rows, top_k, experts, touched):
    assert moe.reads_touched_experts_only(rows, top_k, experts) is touched


def test_the_rule_is_what_the_layer_asks(monkeypatch):
    asked = []
    monkeypatch.setattr(moe, "reads_touched_experts_only", lambda *a: asked.append(a) or False)
    h, router, wg, wu, wd, _ = _layer()
    moe.dropless_moe_ffn(h, router, _stack(wg[:8]), _stack(wu[:8]), _stack(wd[:8]), top_k=K, expert_offset=8, layer=1)
    assert asked == [(T, K, X)]  # the rows, the choices a row, the ROUTER's experts: not the share held
    # a layer's slice would be copied whole in front of the loop: such a call keeps the masked form, unasked
    moe.dropless_moe_ffn(h, router, wg[:8], wu[:8], wd[:8], top_k=K, expert_offset=8)
    assert len(asked) == 1


# ------------------------------------------------- the two forms are one layer


@pytest.mark.parametrize("expert_offset, held", [(0, 32), (0, 8), (12, 8)])
@pytest.mark.parametrize("renormalize", [False, True])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_the_touched_form_equals_the_masked_form_and_reads_no_other_expert(monkeypatch, expert_offset, held, renormalize, scoring):
    """In float32 to 1e-5 on the live rows; rows that are not live get zero.
    The held experts no live row chose are NaN in the touched form's call: one
    read of any of them would show in every row."""
    h, router, wg, wu, wd, bias = _layer(seed=expert_offset + held)
    part = slice(expert_offset, expert_offset + held)
    valid = jnp.asarray([True, False, True, True, False, True])
    kw = dict(top_k=K, renormalize=renormalize, expert_offset=expert_offset, scoring=scoring)
    if scoring == "sigmoid":
        kw.update(bias=bias, scale=2.5)
    want, want_chosen = _masked(monkeypatch, h, router, wg[part], wu[part], wd[part], **kw)
    live = np.asarray(want_chosen)[np.asarray(valid)]
    untouched = jnp.asarray(~np.isin(np.arange(X), live))[part, None, None]
    assert bool(untouched.any())
    poison = lambda w: _stack(jnp.where(untouched, jnp.nan, w[part]))  # noqa: E731
    got, chosen = jax.jit(lambda *a: moe.dropless_moe_ffn(*a, valid=valid, layer=1, **kw))(h, router, poison(wg), poison(wu), poison(wd))
    assert np.array_equal(np.asarray(chosen), np.asarray(want_chosen))
    np.testing.assert_allclose(np.asarray(got)[np.asarray(valid)], np.asarray(want)[np.asarray(valid)], atol=1e-5)
    assert not np.asarray(got)[~np.asarray(valid)].any()
    # the masked form takes its layer of whole stacks too, and is the same layer; a sliced call is the masked form
    masked_stack, _ = _masked(monkeypatch, h, router, _stack(wg[part]), _stack(wu[part]), _stack(wd[part]), layer=1, **kw)
    sliced, _ = moe.dropless_moe_ffn(h, router, wg[part], wu[part], wd[part], valid=valid, **kw)
    assert np.array_equal(np.asarray(masked_stack), np.asarray(want)) and np.array_equal(np.asarray(sliced), np.asarray(want))


def test_a_call_that_touches_no_held_expert_reads_none_and_gives_zero():
    h, router, wg, wu, wd, _ = _layer(seed=3)
    h, router = h.at[:, 0].set(1.0), router.at[:, 24:].set(0.0).at[0, 24:].set(-1e4)  # nobody chooses experts 24..31
    nan = lambda w: jnp.full_like(w[24:], jnp.nan)[None]  # noqa: E731
    y, chosen = moe.dropless_moe_ffn(h, router, nan(wg), nan(wu), nan(wd), top_k=K, renormalize=True, expert_offset=24, layer=0)
    assert int(np.asarray(chosen).max()) < 24 and not np.asarray(y).any()
    # and rows that are not live touch nothing, whatever they would have chosen
    h, router, wg, wu, wd, _ = _layer(seed=4)
    y, _ = moe.dropless_moe_ffn(h, router, jnp.full_like(wg, jnp.nan)[None], wu[None], wd[None], top_k=K, valid=jnp.zeros(T, bool), layer=0)
    assert not np.asarray(y).any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_row_is_the_same_to_the_bit_alone_and_among_others(dtype):
    """Row 5 of a 16-row decode step: the only live row, among 15 live
    others, and among others of which half are not live.  Its terms arrive in
    ascending expert order whoever else is in the call, and another row's
    expert adds an exact zero."""
    rows, experts, top_k = 16, 128, 4  # 2 * 16 * 4 <= 128
    assert moe.reads_touched_experts_only(rows, top_k, experts)
    h, router, wg, wu, wd, _ = (a.astype(dtype) for a in _layer(seed=9, experts=experts, rows=rows))
    held = slice(32, 96)
    stacks = [_stack(w[held]) for w in (wg, wu, wd)]
    layer = jax.jit(lambda h, valid: moe.dropless_moe_ffn(h, router, *stacks, top_k=top_k, renormalize=True, expert_offset=32, valid=valid, layer=1)[0])
    row = np.arange(rows) == 5
    alone = np.asarray(layer(h, jnp.asarray(row)).astype(jnp.float32))
    assert alone[5].any() and not alone[~row].any()
    among = np.asarray(layer(h, jnp.ones(rows, bool)).astype(jnp.float32))
    mixed = np.asarray(layer(h, jnp.asarray(row | (np.arange(rows) % 2 == 0))).astype(jnp.float32))
    others = jnp.asarray(np.random.default_rng(1).normal(size=(rows, E)), dtype).at[5].set(h[5])  # other neighbours, other experts
    moved = np.asarray(layer(others, jnp.ones(rows, bool)).astype(jnp.float32))
    for got in (among, mixed, moved):
        assert np.array_equal(got[5], alone[5])
