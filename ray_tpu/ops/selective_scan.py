"""The Mamba-1 selective scan over per-slot state: a Pallas kernel on TPU, a
``lax.scan`` elsewhere.

Per channel d and state index n, with everything float32:

    h[n, d] <- exp(dt[t, d] * A[n, d]) * h[n, d] + dt[t, d] * B[t, n] * u[t, d]
    y[t, d]  = sum_n h[n, d] * C[t, n] + D[d] * u[t, d]

Every element of the state has its own input-dependent decay, so there is no
matmul form.  Written plainly a chunk of T rows materialises ``exp(dt A)`` and
``dt B u`` as two arrays [T, d_inner, N] (168 MB a layer at T = 256, d_inner =
5120, N = 16); the kernel instead keeps one tile of the state -- N vregs of
8 x 128 channels -- in registers, walks the rows in order, and moves only its
inputs and outputs: u, dt, y once each, B and C as scalars, the state once in
and once out.

Layout, the same for both forms: channels are viewed as [R, 128] (d_inner =
R * 128, channel c at [c // 128, c % 128]) so that a tile of 8 x 128 channels
is one dense vreg and B[t, n], C[t, n] are scalars against it.  The state is a
member of the engine's pool, [layers, slots, N, R, 128] float32, passed whole
and aliased into the result: a call reads and writes the blocks of ITS layer
and ITS rows' slots and nothing else moves (no slice out, no update back).

A row with dt = 0 leaves the state bit for bit as it was (decay exp(0) = 1,
input 0); a row flagged ``fresh`` starts from a zero state whatever the slot
held.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import _on_tpu

LANES = 128
SUBLANES = 8


def channel_tiles(x):
    """[..., d_inner] -> [..., R, 128]."""
    return x.reshape(*x.shape[:-1], x.shape[-1] // LANES, LANES)


def _plain(u, dt, Bm, Cm, A, D, h0):
    """One batch row, token by token: u, dt [T, R, 128]; Bm, Cm [T, N]; A, h0
    [N, R, 128]; D [R, 128] -> (y [T, R, 128], h)."""

    def step(h, row):
        u_t, dt_t, b_t, c_t = row
        h = jnp.exp(dt_t[None] * A) * h + (dt_t * u_t)[None] * b_t[:, None, None]
        return h, D * u_t + (h * c_t[:, None, None]).sum(0)

    h, y = lax.scan(step, h0, (u, dt, Bm, Cm))
    return y, h


def _scan_kernel(T, N, Bb, Rb, tile, meta_ref, b_ref, c_ref, u_ref, dt_ref, a_ref, d_ref, s_in_ref, y_ref, s_out_ref):
    """One grid step: ``Bb`` batch rows x ``Rb`` channel rows, a tile of
    ``tile`` channel rows at a time.  meta = [first slot, fresh flags ...]
    (SMEM); b, c flat [B * T * N] (SMEM)."""
    from jax.experimental import pallas as pl

    b0 = pl.program_id(0) * Bb
    tiles = Rb // tile

    def one_tile(i, carry):
        bi, rt = i // tiles, i % tiles
        rows = pl.ds(pl.multiple_of(rt * tile, tile), tile)
        a = a_ref[:, rows, :]  # [N, tile, 128]
        d = d_ref[rows, :]
        row = b0 + bi
        fresh = meta_ref[1 + row] != 0
        h0 = jnp.where(fresh, 0.0, s_in_ref[bi, :, rows, :])
        base = row * T

        def step(t, h):
            dt_t = dt_ref[bi, t, rows, :]
            u_t = u_ref[bi, t, rows, :]
            dtu = dt_t * u_t
            y = d * u_t
            off = (base + t) * N
            new = []
            for n in range(N):
                hn = jnp.exp(dt_t * a[n]) * h[n] + dtu * b_ref[off + n]
                y = y + hn * c_ref[off + n]
                new.append(hn)
            y_ref[bi, t, rows, :] = y
            return tuple(new)

        h = lax.fori_loop(0, T, step, tuple(h0[n] for n in range(N)))
        for n in range(N):
            s_out_ref[bi, n, rows, :] = h[n]
        return carry

    lax.fori_loop(0, Bb * tiles, one_tile, 0)


def _kernel_call(u, dt, Bm, Cm, A, D, state, layer: int, slot, fresh, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, R, _ = u.shape
    N = A.shape[0]
    tile = SUBLANES if R % SUBLANES == 0 else R
    if slot is None:
        # a decode step: row b is slot b.  A few slots' whole states a grid step (1.3 MB at the published widths)
        Bb, Rb = next(k for k in (4, 2, 1) if B % k == 0), R
        first = jnp.zeros((1,), jnp.int32)
    else:
        # a prefill chunk of one slot: a tile of channels a grid step, so that u, dt and y stream under the compute
        Bb, Rb = 1, tile
        first = jnp.asarray(slot, jnp.int32).reshape(1)
    meta = jnp.concatenate([first, fresh.astype(jnp.int32)])
    rows = lambda i, j, meta: (i, 0, j, 0)  # noqa: E731
    pool = pl.BlockSpec((None, Bb, N, Rb, LANES), lambda i, j, meta: (layer, meta[0] + i, 0, j, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, T, N, Bb, Rb, tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // Bb, R // Rb),
            in_specs=[
                smem, smem,
                pl.BlockSpec((Bb, T, Rb, LANES), rows), pl.BlockSpec((Bb, T, Rb, LANES), rows),
                pl.BlockSpec((N, Rb, LANES), lambda i, j, meta: (0, j, 0)), pl.BlockSpec((Rb, LANES), lambda i, j, meta: (j, 0)),
                pool,
            ],
            out_specs=[pl.BlockSpec((Bb, T, Rb, LANES), rows), pool],
        ),
        out_shape=[jax.ShapeDtypeStruct(u.shape, jnp.float32), jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={7: 1},  # the pool member, in place
        interpret=interpret,
        name="ssm_scan",
    )(meta, Bm.reshape(-1), Cm.reshape(-1), u, dt, A, D, state)
    return y, state


def selective_scan(u, dt, Bm, Cm, A, D, state, layer: int, slot=None, fresh=None, *, impl: str = "auto"):
    """The scan of ``layer`` over a call's rows, from and into the pool's state.

    u, dt [B, T, R, 128] float32 (``channel_tiles``); Bm, Cm [B, T, N]; A [N,
    R, 128] (negative); D [R, 128]; state [layers, slots, N, R, 128] float32;
    ``slot`` None: row b is slot b (a decode step, B == slots), else the
    scalar slot of a call with B == 1 (a prefill chunk); fresh [B] bool.
    Returns (y [B, T, R, 128] float32, state).

    impl: "auto" (the kernel on TPU, the plain form elsewhere) | "kernel" |
    "interpret" (the kernel in the Pallas interpreter: tests) | "plain"."""
    if impl not in ("auto", "kernel", "interpret", "plain"):
        raise ValueError(f"unknown selective_scan impl {impl!r}")
    B = u.shape[0]
    if B != (state.shape[1] if slot is None else 1):
        raise ValueError(f"{B} rows against {state.shape[1]} slots: a decode step has one row a slot, a prefill chunk one row and its slot")
    fresh = jnp.zeros((B,), bool) if fresh is None else fresh
    f32 = jnp.float32
    u, dt, Bm, Cm, A, D = (a.astype(f32) for a in (u, dt, Bm, Cm, A, D))
    if impl == "kernel" or impl == "interpret" or (impl == "auto" and _on_tpu()):
        return _kernel_call(u, dt, Bm, Cm, A, D, state, layer, slot, fresh, impl == "interpret")
    h0 = state[layer] if slot is None else lax.dynamic_index_in_dim(state[layer], slot, 0)
    h0 = jnp.where(fresh[:, None, None, None], 0.0, h0)
    y, h = jax.vmap(_plain, in_axes=(0, 0, 0, 0, None, None, 0))(u, dt, Bm, Cm, A, D, h0)
    return y, (state.at[layer].set(h) if slot is None else state.at[layer, slot].set(h[0]))
