"""Jamba (``model_type: jamba``; transformers' ``modeling_jamba.py`` is the
published description), serving path: a decoder whose layers are of two
kinds.  Layer ``i`` (from 0) attends where ``i % attn_layer_period ==
attn_layer_offset`` and is a Mamba-1 selective state-space mixer otherwise
(``JambaMambaMixer``: the Mamba-1 mixer with RMSNorms on dt, B and C); every
layer ends in the same dense SwiGLU (``num_experts: 1``); the head is the
embedding, transposed.

    x += mixer(norm(x));  x += ffn(norm(x))        norm: w * rmsnorm(x), eps 1e-6

``JambaModel`` is a ``LlamaModel``: the engine's two paged programs
(``prefill_chunk_paged`` / ``decode_step_paged``), the page write, the
blockwise walk of the page table (``_paged_attend``, here 20 query heads on
ONE KV head of 128), the dense ``_ffn`` and the greedy sampler are that
class's.  What this file adds is ``_paged_forward`` over layers of two kinds,
the two mixers, the tied head, and the pool that goes with them: K/V pages for
the attending layers only, and per SLOT a float32 state [L_mamba, slots, N,
R, 128] and a conv window [L_mamba, slots, k - 1, R, 128] for the Mamba
layers (``init_pages``).

The Mamba mixer, d_inner = expand * dim, N = d_state, per channel d:

    [u | z] = h W_in;  u = silu(conv(u) + b_conv)        causal, depthwise, kernel d_conv
    [dt_r | B | C] = u W_x;  each through its own RMSNorm;  dt = softplus(dt_r W_dt + b_dt)
    h[n, d] <- exp(dt[d] A[n, d]) h[n, d] + dt[d] B[n] u[d];   A = -exp(A_log)
    y[d] = sum_n h[n, d] C[n] + D[d] u[d];  out = (y * silu(z)) W_out

dt, A, B, C, the exponentials and the state are float32 (``ops/selective_scan.py``
is the recurrence: a Pallas kernel on TPU).  The attention mixer has no
positional encoding, no QK-norm and no bias.

Layout against the published tensors (a checkpoint loader transposes once;
``benchmarks/configs`` states it under ``assumed``): ``w_in`` columns are [u |
z] and ``w_x`` columns [dt_r | B | C], as published; ``A_log`` is [N, d_inner]
(published [d_inner, N]) and the state [N, d_inner / 128, 128], so that
d_inner lies on the lanes.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.models.llama import LlamaConfig, LlamaModel, _rms_norm, conv_window_after, conv_window_taps
from ray_tpu.ops import selective_scan as ssm


@dataclasses.dataclass(frozen=True)
class JambaConfig(LlamaConfig):
    """Defaults are the published sizes of AI21-Jamba2-3B."""

    vocab_size: int = 65536
    dim: int = 2560
    n_layers: int = 28
    n_heads: int = 20
    n_kv_heads: int = 1
    hidden_dim: int = 8192
    max_seq_len: int = 2048
    norm_eps: float = 1e-6
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160

    def __post_init__(self):
        if self.d_inner % ssm.LANES:
            raise ValueError(f"d_inner = {self.d_inner} is not whole lanes of {ssm.LANES}")
        if self.n_heads % self.n_kv_heads or not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError("query heads must be a multiple of KV heads, and the attention offset lie inside its period")

    def build_model(self) -> "JambaModel":
        return JambaModel(self)

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """"attn" or "mamba" for each layer, in order."""
        return tuple("attn" if i % self.attn_layer_period == self.attn_layer_offset else "mamba" for i in range(self.n_layers))

    def _layer_params(self) -> Dict[str, int]:
        E, Dn, N, R, K = self.dim, self.d_inner, self.d_state, self.dt_rank, self.d_conv
        kv = self.n_kv_heads * self.head_dim
        return {
            # in_proj, conv and its bias, x_proj, the three inner norms, dt_proj and its bias, A_log, D, out_proj
            "mamba": E * 2 * Dn + Dn * K + Dn + Dn * (R + 2 * N) + (R + 2 * N) + R * Dn + Dn + Dn * N + Dn + Dn * E,
            "attn": 2 * E * E + 2 * E * kv,
            "ffn": 3 * E * self.hidden_dim + 2 * E,  # and the two block norms
        }

    def num_params(self) -> int:
        """As published: the embedding is the head too and counts once."""
        n = self._layer_params()
        return int(self.vocab_size * self.dim + self.dim + sum(n[k] + n["ffn"] for k in self.layer_kinds))


class JambaModel(LlamaModel):
    config: JambaConfig

    # -------------------------------------------------------------- params

    def init(self, rng: jax.Array) -> Dict[str, Any]:
        """Three stacks, because the two mixers do not stack into one:
        ``mamba`` [L_mamba, ...], ``attn`` [L_attn, ...] and ``ffn`` [L, ...]
        (the SwiGLU every layer ends in, with both block norms); no
        ``out_head``.  Mamba-1's own initial values where it has them
        (``assumed`` in the configuration file)."""
        cfg = self.config
        E, V, H, pd = cfg.dim, cfg.padded_vocab, cfg.hidden_dim, cfg.param_dtype
        Dn, N, R, K = cfg.d_inner, cfg.d_state, cfg.dt_rank, cfg.d_conv
        kinds = cfg.layer_kinds
        L, Lm, La = len(kinds), kinds.count("mamba"), kinds.count("attn")
        kv = cfg.n_kv_heads * cfg.head_dim
        k = iter(jax.random.split(rng, 16))
        std, out_std = 0.02, 0.02 / math.sqrt(2 * L)

        def norm(shape, s=std):
            return (jax.random.normal(next(k), shape) * s).astype(pd)

        def uniform(shape, bound):
            return jax.random.uniform(next(k), shape, minval=-bound, maxval=bound).astype(pd)

        # dt = softplus(b_dt) log-uniform in [0.001, 0.1]; A = 1..N for every channel; D = 1
        dt = jnp.exp(jax.random.uniform(next(k), (Lm, Dn)) * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        mamba = {
            "w_in": norm((Lm, E, 2 * Dn)),
            "conv_w": uniform((Lm, K, Dn), K**-0.5),
            "conv_b": uniform((Lm, Dn), K**-0.5),
            "w_x": norm((Lm, Dn, R + 2 * N)),
            "dt_norm": jnp.ones((Lm, R), pd), "b_norm": jnp.ones((Lm, N), pd), "c_norm": jnp.ones((Lm, N), pd),
            "w_dt": uniform((Lm, R, Dn), R**-0.5),
            "b_dt": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None], (Lm, N, Dn)).astype(pd),
            "D": jnp.ones((Lm, Dn), pd),
            "w_out": norm((Lm, Dn, E), out_std),
        }
        attn = {"wq": norm((La, E, E)), "wk": norm((La, E, kv)), "wv": norm((La, E, kv)), "wo": norm((La, E, E), out_std)}
        ffn = {
            "attn_norm": jnp.ones((L, E), pd), "ffn_norm": jnp.ones((L, E), pd),
            "w_gate": norm((L, E, H)), "w_up": norm((L, E, H)), "w_down": norm((L, H, E), out_std),
        }
        return {"tok_emb": norm((V, E)), "final_norm": jnp.ones((E,), pd), "mamba": mamba, "attn": attn, "ffn": ffn}

    def param_pspecs(self, mesh=None) -> Dict[str, Any]:
        """Everything whole on every device: one KV head does not divide, and
        ``ShardedLLM`` refuses ``tp > 1`` for it."""
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        return jax.tree.map(lambda a: P(*([None] * a.ndim)), shapes)

    # --------------------------------------------------------------- pool

    def init_pages(self, num_pages: int, page_size: int, num_slots: int = 0) -> Tuple:
        """K/V pages of the ATTENDING layers only [L_attn, NP, PS, KV, D],
        and per slot the Mamba layers' float32 state [L_mamba, slots, N, R,
        128] (``ops/selective_scan.py``'s layout: d_inner as R tiles of 128
        channels) and conv window [L_mamba, slots, k - 1, R, 128].  A slot is
        a MAJOR axis of both, so a chunk reads and writes its slot's part
        where it lies (with the window [.., slots, k - 1, d_inner] the v5e
        compiler re-laid the whole member, 102 MB, in and out of every chunk
        call).  No routing counter: ``pool_roles`` says which member is
        what."""
        cfg = self.config
        if num_slots <= 0:
            raise ValueError("a model with per-slot state must be told the number of slots")
        kinds = cfg.layer_kinds
        Lm = kinds.count("mamba")
        shape = (kinds.count("attn"), num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
        return (
            jnp.zeros(shape, cfg.compute_dtype),
            jnp.zeros(shape, cfg.compute_dtype),
            jnp.zeros((Lm, num_slots, cfg.d_state, cfg.d_inner // ssm.LANES, ssm.LANES), jnp.float32),
            jnp.zeros((Lm, num_slots, cfg.d_conv - 1, cfg.d_inner // ssm.LANES, ssm.LANES), cfg.compute_dtype),
        )

    def pool_pspecs(self) -> Tuple:
        return (P(), P(), P(), P())

    def pool_roles(self) -> Tuple[str, ...]:
        return ("pages", "pages", "state", "state")

    # ------------------------------------------------------------- forward

    def apply(self, params, tokens, mesh=None):
        raise NotImplementedError("JambaModel has the serving path only (the paged programs)")

    def _logits(self, params, x):
        """The tied head: contracted against the embedding where it lies
        ([V, E], no transposed twin)."""
        return jnp.einsum("...e,ve->...v", x, params["tok_emb"].astype(self.config.compute_dtype))

    def _attn(self, x, fp, ap, ai: int, pages, wpage, woff, tables, q_pos, q_valid, n_blocks):
        """Attention mixer ``ai`` (its index among the attending layers, and
        in the K/V pool): no rotary, no QK-norm; the shared page write and
        walk."""
        cfg = self.config
        cd = cfg.compute_dtype
        B, S, _ = x.shape
        H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        kp, vp = pages
        h = _rms_norm(x, fp["attn_norm"].astype(jnp.float32), cfg.norm_eps).astype(cd)
        # behind the barrier the three stay plain 2-D matmuls that read their weights where they lie (LlamaModel._qkv)
        q, k, v = lax.optimization_barrier((h @ ap["wq"].astype(cd), h @ ap["wk"].astype(cd), h @ ap["wv"].astype(cd)))
        kp = self._paged_write(kp, ai, wpage, woff, k.reshape(-1, KV, D))
        vp = self._paged_write(vp, ai, wpage, woff, v.reshape(-1, KV, D))
        attn = self._paged_attend(q.reshape(B, S, H, D), kp, vp, ai, tables, q_pos, q_valid, n_blocks)
        return x + attn @ ap["wo"].astype(cd), (kp, vp)

    def _mamba(self, x, fp, mp, mi: int, state, conv, slot, q_pos, q_valid):
        """Mamba mixer ``mi`` (its index among the Mamba layers, and in the
        per-slot state).  x [B, S, E]: a decode step (B = slots, S = 1,
        ``slot`` None: row b is slot b) or a prefill chunk (B = 1, S = chunk,
        of slot ``slot``).  A row that begins a sequence (valid, at position
        0) starts from a zero state and window; rows that are not valid (an
        inactive slot, a chunk's padded tail) have dt = 0 and leave both as
        they were."""
        cfg = self.config
        cd, f32 = cfg.compute_dtype, jnp.float32
        B, S, _ = x.shape
        Dn, N, R = cfg.d_inner, cfg.d_state, cfg.dt_rank

        h = _rms_norm(x, fp["attn_norm"].astype(f32), cfg.norm_eps).astype(cd)
        uz = h @ mp["w_in"].astype(cd)
        u, z = uz[..., :Dn], uz[..., Dn:]

        # causal depthwise conv over the last k-1 inputs and the call's own, channels as the scan's tiles
        tiles = ssm.channel_tiles
        y, seq, fresh = conv_window_taps(conv, mi, slot, u, mp["conv_w"], q_pos, q_valid, tiles)
        u = jax.nn.silu(y + tiles(mp["conv_b"].astype(f32)))
        conv = conv_window_after(conv, mi, slot, seq, q_valid)

        xdbc = jnp.matmul(u.reshape(B, S, Dn).astype(cd), mp["w_x"].astype(cd), preferred_element_type=f32)
        dt_r = _rms_norm(xdbc[..., :R], mp["dt_norm"].astype(f32), cfg.norm_eps)
        Bm = _rms_norm(xdbc[..., R : R + N], mp["b_norm"].astype(f32), cfg.norm_eps)
        Cm = _rms_norm(xdbc[..., R + N :], mp["c_norm"].astype(f32), cfg.norm_eps)
        dt = jnp.matmul(dt_r.astype(cd), mp["w_dt"].astype(cd), preferred_element_type=f32) + mp["b_dt"].astype(f32)
        dt = jax.nn.softplus(dt) * q_valid[..., None]  # a row that is not valid neither decays the state nor writes to it
        A = -jnp.exp(mp["A_log"].astype(f32))
        with jax.named_scope("ssm_scan"):
            y, state = ssm.selective_scan(u, tiles(dt), Bm, Cm, tiles(A), tiles(mp["D"].astype(f32)), state, mi, slot, fresh)
        y = (y.reshape(B, S, Dn) * jax.nn.silu(z.astype(f32))).astype(cd)
        return x + y @ mp["w_out"].astype(cd), state, conv

    def _paged_forward(self, params, x, pages, wpage, woff, tables, q_pos, q_valid, slot=None):
        cfg = self.config
        kp, vp, state, conv = pages
        tables, n_blocks = self._walk_blocks(tables, kp.shape[2], q_pos, q_valid)
        n_attn = n_mamba = 0
        for i, kind in enumerate(cfg.layer_kinds):
            fp = jax.tree.map(lambda p: p[i], params["ffn"])
            if kind == "attn":
                ap = jax.tree.map(lambda p: p[n_attn], params["attn"])
                with jax.named_scope("attn"):
                    x, (kp, vp) = self._attn(x, fp, ap, n_attn, (kp, vp), wpage, woff, tables, q_pos, q_valid, n_blocks)
                n_attn += 1
            else:
                mp = jax.tree.map(lambda p: p[n_mamba], params["mamba"])
                with jax.named_scope("mamba"):
                    x, state, conv = self._mamba(x, fp, mp, n_mamba, state, conv, slot, q_pos, q_valid)
                n_mamba += 1
            x, _ = self._ffn(x, fp)
        x = _rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
        return x.astype(cfg.compute_dtype), (kp, vp, state, conv)
