"""Attention ops: the Pallas splash kernel on TPU, an einsum composition
elsewhere.

The hot op of the model zoo.  On TPU a sequence the kernel's blocks tile
(a multiple of 128, at least 512) goes to jax's in-tree splash-attention
kernel (VMEM-blocked online softmax — no [S, S] score tensor ever hits
HBM; differentiable via its custom_vjp).  Other platforms (tests, dryruns)
and other sequence lengths get a plain einsum composition; which one a
compiled program holds is visible in its lowered text (``tpu_custom_call``),
and chip_smoke.py fails a training step that holds the einsum.

Layouts: this module takes [batch, seq, heads, head_dim] (the model's
native layout) and transposes at the boundary to the kernel's
[batch, heads, seq, head_dim].
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp


def _xla_causal_attention(q, k, v, sm_scale, scores_dtype=jnp.float32):
    S = q.shape[1]
    # scores_dtype sets what the QK^T matmul writes to HBM: f32 is the safe
    # default; bf16 halves the [S,S] tensor traffic (softmax still reduces
    # in f32 internally via xla)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=scores_dtype
    ) * jnp.asarray(sm_scale, scores_dtype)
    mask = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(mask[None, None], scores, jnp.asarray(-1e30, scores_dtype))
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _on_tpu() -> bool:
    # a backend that fails to initialise raises here: the caller asked for
    # a device, and an answer of "not on TPU" would run it on the reference
    return jax.devices()[0].platform == "tpu"


@functools.lru_cache(maxsize=8)
def _splash_kernel(n_heads: int, seq: int, block_q: int, block_kv: int, interpret: bool = False):
    """Splash-attention causal kernel (pallas), cached per shape.
    ``interpret`` runs it in the Pallas interpreter: tests on the CPU only.

    Measured on v5e (GPT-2 base: B=16, H=12, S=1024, D=64): fused-bwd splash
    at 512/512 blocks runs fwd+bwd in 8.2 ms vs 10.7 ms for the fused-XLA
    path — and, unlike XLA, leaves no [B,H,S,S] score/prob tensors in HBM
    (neither live nor saved-for-backward), which is what frees the chip to
    run remat-free at batch 32+."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as smask,
    )

    bq = min(block_q, seq)
    bkv = min(block_kv, seq)
    mask = smask.MultiHeadMask([smask.CausalMask((seq, seq)) for _ in range(n_heads)])
    # SEQ_MINOR k/v layout: measured 6.2 ms vs 8.5 ms fwd+bwd (v5e, GPT-2
    # base shapes) — with D=64 the head-minor layout leaves the 128-lane
    # registers half-empty on the K/V side of both matmuls
    bs = sk.BlockSizes(
        block_q=bq,
        block_kv=bkv,
        block_kv_compute=bkv,
        block_q_dkv=bq,
        block_kv_dkv=bkv,
        block_kv_dkv_compute=bkv,
        use_fused_bwd_kernel=True,
        k_layout=sk.QKVLayout.SEQ_MINOR,
        v_layout=sk.QKVLayout.SEQ_MINOR,
    )
    # residuals named so remat policies can SAVE them: without this, a
    # jax.checkpoint around the layer re-runs the whole fwd kernel inside
    # the backward pass (custom-call outputs aren't "dots", so dot-saving
    # policies recompute them)
    # the kernel object holds mask arrays and is cached across traces: built
    # under a jit trace they would be that trace's tracers, and the next jit
    # of the same shape (a second step function, a grad) would die on them
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha(
            mask,
            block_sizes=bs,
            head_shards=1,
            q_seq_shards=1,
            residual_checkpoint_name="splash_residuals",
            interpret=interpret,
        )


def _splash_causal_attention(
    q, k, v, sm_scale, mesh=None, block_q=512, block_kv=512, interpret=False
):
    """q,k,v: [B, S, H, D] → [B, S, H, D] via the splash kernel.

    XLA cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"), so over a mesh of several devices the call
    is wrapped in a shard_map: each device runs the kernel on its slice of
    the batch (dp, fsdp) and of the heads (tp), whole sequences.  Inside a
    region that is already manual (the pipeline's shard_map) the kernel is
    called as it is."""
    B, S, H, D = q.shape
    # block sizes must divide S; largest divisor ≤ the tuned default wins
    bq = next((b for b in (block_q, 256, 128) if S % b == 0), None)
    bkv = next((b for b in (block_kv, 256, 128) if S % b == 0), None)
    if bq is None or bkv is None:
        raise ValueError(
            f"splash attention needs seq length divisible by 128; got S={S} "
            f"(use attention_impl='xla' or pad the sequence)"
        )

    shape = dict(mesh.shape) if mesh is not None else {}
    wrap = (
        mesh is not None
        and mesh.size > 1
        and not jax.sharding.get_abstract_mesh().manual_axes
    )
    tp = shape.get("tp", 1) if wrap else 1
    kernel = _splash_kernel(H // tp, S, bq, bkv, interpret)

    def local(q, k, v):
        qt = (q * q.dtype.type(sm_scale)).transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        return jax.vmap(kernel)(qt, kt, vt).transpose(0, 2, 1, 3)

    if not wrap:
        return local(q, k, v)
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.mesh import shard_map_compat

    batch_axes = tuple(a for a in ("dp", "fsdp") if shape.get(a, 1) > 1)
    spec = P(batch_axes or None, None, "tp" if tp > 1 else None, None)
    return shard_map_compat(
        local, mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    sm_scale: Optional[float] = None,
    impl: str = "auto",
    scores_dtype=jnp.float32,
    mesh=None,
) -> jax.Array:
    """Causal MHA.  q,k,v: [B, S, H, D] → [B, S, H, D].

    impl: "auto" (splash kernel on TPU, xla elsewhere) | "splash" | "xla".
    mesh: the mesh the arrays are sharded over, if any; the splash path
    needs it to place the kernel (the einsum path is partitioned by XLA).
    """
    if impl not in ("auto", "splash", "xla"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if impl == "splash" or (
        impl == "auto" and _on_tpu() and q.shape[1] >= 512 and q.shape[1] % 128 == 0
    ):
        return _splash_causal_attention(q, k, v, sm_scale, mesh)
    return _xla_causal_attention(q, k, v, sm_scale, scores_dtype)
