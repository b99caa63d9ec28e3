"""Qwen3-Next (``model_type: qwen3_next``; transformers'
``modeling_qwen3_next.py`` is the published description), serving path: a
decoder whose layers are of two kinds.  Layer ``i`` (from 0) is gated
softmax attention where ``(i + 1) % full_attention_interval == 0`` and a
Gated DeltaNet otherwise (3 linear : 1 full); every layer ends in the same
block of routed experts plus one shared expert.

    x += mixer(norm(x));  x += moe(norm(x))        norm: (1 + w) * rmsnorm(x)

``Qwen3NextModel`` is a ``LlamaModel``: the engine's two paged programs
(``prefill_chunk_paged`` / ``decode_step_paged``), the page write, the
blockwise walk of the page table (``_paged_attend``, here at 8 query heads
a KV head and head_dim 256) and the greedy sampler are that class's, and
the routed layer is ``parallel/moe.py dropless_moe_ffn``, told which of the
router's experts this device holds.  What this file adds is
``_paged_forward`` over layers of two kinds and the pool that goes with
them: K/V pages for the full layers only, and per SLOT a float32 recurrent
state [L_lin, slots, Hv, Dk, Dv] and a conv window [L_lin, slots, k-1,
channels] for the Gated DeltaNet layers (``init_pages``).

The Gated DeltaNet, per value head (q and k of key head ``j // (Hv/Hk)``),
state S [Dk, Dv] zero at a sequence's start:

    S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);  S <- S + k_t d_t^T;  o_t = S^T q_t

``gated_delta_step`` is that recurrence for one token (the decode step);
``gated_delta_chunked`` computes the same over blocks of ``GDN_BLOCK``
tokens with matmuls, the state passed from block to block (the prefill
chunk).  g, beta, the cumulative decays, the l2 norms and the state are
float32, and the float32 contractions run at the highest precision (the
TPU's default would round their inputs to bf16).

Layout of the fused projections (a checkpoint loader permutes columns once;
``benchmarks/configs`` states it under ``assumed``): ``w_qkvz`` columns are
[q (Hk*Dk) | k (Hk*Dk) | v (Hv*Dv) | z (Hv*Dv)], heads major within each;
``w_ba`` is [b (Hv) | a (Hv)]; ``wq`` of a full layer is per head [query
(D) | gate (D)], as published.  Rotary is the published pairing (i, i +
rot/2) over the first ``partial_rotary_factor`` of a head.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ray_tpu.models.llama import LlamaConfig, LlamaModel

GDN_BLOCK = 64  # tokens in one block of the chunked Gated DeltaNet
_HI = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig(LlamaConfig):
    """Defaults are the published widths of Qwen3-Next-80B-A3B.
    ``hidden_dim`` is one routed expert's width; ``n_experts`` the experts
    HELD here (``expert_offset`` .. + ``n_experts`` of the router's
    ``n_routed_experts``), one device's share of an expert-parallel
    deployment, or all of them."""

    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 16
    n_kv_heads: int = 2
    hidden_dim: int = 512
    max_seq_len: int = 16384
    rope_theta: float = 1e7
    norm_eps: float = 1e-6
    n_experts: int = 512
    n_experts_per_tok: int = 10
    head_dim: int = 256  # a key of its own: n_heads * head_dim != dim
    partial_rotary_factor: float = 0.25
    full_attention_interval: int = 4
    n_routed_experts: int = 512
    expert_offset: int = 0
    norm_topk_prob: bool = True
    shared_hidden_dim: int = 512
    lin_key_heads: int = 16
    lin_value_heads: int = 32
    lin_key_dim: int = 128
    lin_value_dim: int = 128
    conv_kernel: int = 4

    def __post_init__(self):
        if not 0 < self.n_experts_per_tok <= self.n_routed_experts:
            raise ValueError(f"n_experts_per_tok={self.n_experts_per_tok} must lie in 1..n_routed_experts={self.n_routed_experts}")
        if not (0 <= self.expert_offset and 0 < self.n_experts and self.expert_offset + self.n_experts <= self.n_routed_experts):
            raise ValueError(
                f"held experts {self.expert_offset}..{self.expert_offset + self.n_experts} are not among the router's {self.n_routed_experts}"
            )
        if self.lin_value_heads % self.lin_key_heads or self.n_heads % self.n_kv_heads:
            raise ValueError("value heads must be a multiple of key heads, query heads of KV heads")

    def build_model(self) -> "Qwen3NextModel":
        return Qwen3NextModel(self)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """"full" or "linear" for each layer, in order."""
        return tuple("full" if (i + 1) % self.full_attention_interval == 0 else "linear" for i in range(self.n_layers))

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def conv_dim(self) -> int:
        """Channels the causal conv runs over: q, k and v of a linear layer."""
        return 2 * self.lin_key_heads * self.lin_key_dim + self.lin_value_heads * self.lin_value_dim

    def _layer_params(self) -> Dict[str, int]:
        """Parameters of one layer's parts, from the published keys."""
        E, H, D, KV = self.dim, self.n_heads, self.head_dim, self.n_kv_heads
        v_dim = self.lin_value_heads * self.lin_value_dim
        return {
            "linear": E * (self.conv_dim + v_dim) + E * 2 * self.lin_value_heads + self.conv_dim * self.conv_kernel
            + 2 * self.lin_value_heads + self.lin_value_dim + v_dim * E,
            "full": E * H * 2 * D + 2 * E * KV * D + H * D * E + 2 * D,
            # the two block norms, router, shared expert and its gate
            "outside_experts": 2 * E + E * self.n_routed_experts + 3 * E * self.shared_hidden_dim + E,
            "expert": 3 * E * self.hidden_dim,
        }

    def num_params(self) -> int:
        """As published: embedding and head at ``vocab_size`` rows (the
        tree pads both to ``padded_vocab``)."""
        n = self._layer_params()
        kinds = self.layer_kinds
        mixers = sum(n[k] for k in kinds)
        return int(2 * self.vocab_size * self.dim + self.dim + mixers + len(kinds) * (n["outside_experts"] + self.n_experts * n["expert"]))

    def active_params_per_token(self) -> int:
        """All but the held experts a token is not routed to: of its
        ``n_experts_per_tok`` choices the held share falls here."""
        routed_here = self.n_experts_per_tok * self.n_experts / self.n_routed_experts
        return int(self.num_params() - self.n_layers * (self.n_experts - routed_here) * self._layer_params()["expert"])


def _zrms_norm(x, w, eps):
    """The zero-centred RMSNorm: (1 + w) * x * rsqrt(mean(x^2) + eps), float32."""
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt((x32**2).mean(-1, keepdims=True) + eps) * (1.0 + w.astype(jnp.float32))


def _l2_norm(x, eps=1e-6):
    return x * lax.rsqrt((x**2).sum(-1, keepdims=True) + eps)


def _partial_rope(x, positions, theta, rot: int):
    """x [..., seq, heads, D]: rotate the pairs (i, i + rot/2) of the first
    ``rot`` dimensions by pos * theta^(-2i/rot); the rest passes."""
    freqs = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions[..., None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., : rot // 2], x32[..., rot // 2 : rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x32[..., rot:]], axis=-1).astype(x.dtype)


def gated_delta_step(q, k, v, g, beta, S):
    """One token of the recurrence, per head.  q, k [H, Dk]; v [H, Dv];
    g, beta [H]; S [H, Dk, Dv], all float32 -> (o [H, Dv], S).  g = 0 and
    beta = 0 leave S bit for bit as it was."""
    S = S * jnp.exp(g)[:, None, None]
    d = beta[:, None] * (v - (S * k[:, :, None]).sum(1))
    S = S + k[:, :, None] * d[:, None, :]
    return (S * q[:, :, None]).sum(1), S


def gated_delta_chunked(q, k, v, g, beta, S, block: int = GDN_BLOCK):
    """The same recurrence over T tokens (a multiple of ``block``) in blocks.
    q, k [T, H, Dk]; v [T, H, Dv]; g, beta [T, H]; S [H, Dk, Dv], float32
    -> (o [T, H, Dv], S after the last token).

    Within a block starting from S0, with c_t the running sum of g:
    d = (I + A)^-1 (beta v - beta e^c (k S0)),  A[t, s] = beta_t e^(c_t -
    c_s) (k_t . k_s) for s < t;  o_t = e^c_t q_t S0 + sum_{s <= t}
    e^(c_t - c_s) (q_t . k_s) d_s;  S = e^c_C S0 + sum_s e^(c_C - c_s) k_s
    d_s^T.  Everything but the three products with S0 is independent of the
    blocks before and computed for all blocks at once; a scan carries S."""
    T, H, Dk = q.shape
    Dv = v.shape[-1]
    nb = T // block

    def blocks(a):  # [T, H, ...] -> [nb, H, block, ...]
        return jnp.moveaxis(a.reshape(nb, block, *a.shape[1:]), 1, 2)

    q, k, v, g, beta = (blocks(a) for a in (q, k, v, g, beta))
    c = jnp.cumsum(g, axis=-1)  # [nb, H, C]
    upto = jnp.tril(jnp.ones((block, block), bool))
    decay = jnp.exp(jnp.where(upto, c[..., :, None] - c[..., None, :], -jnp.inf))  # [nb, H, t, s], s <= t
    kk = jnp.einsum("bhtk,bhsk->bhts", k, k, precision=_HI)
    A = jnp.where(jnp.tril(upto, -1), beta[..., None] * decay * kk, 0.0)
    rhs = jnp.concatenate([beta[..., None] * v, (beta * jnp.exp(c))[..., None] * k], axis=-1)
    sol = jax.scipy.linalg.solve_triangular(A + jnp.eye(block, dtype=A.dtype), rhs, lower=True, unit_diagonal=True)
    u, w = sol[..., :Dv], sol[..., Dv:]  # d = u - w S0
    qk = jnp.einsum("bhtk,bhsk->bhts", q, k, precision=_HI) * decay
    q_in = q * jnp.exp(c)[..., None]
    c_end = c[..., -1]  # [nb, H]
    k_out = k * jnp.exp(c_end[..., None] - c)[..., None]

    def step(S, xs):
        u, w, qk, q_in, k_out, c_end = xs
        d = u - jnp.einsum("htk,hkv->htv", w, S, precision=_HI)
        o = jnp.einsum("htk,hkv->htv", q_in, S, precision=_HI) + jnp.einsum("hts,hsv->htv", qk, d, precision=_HI)
        S = jnp.exp(c_end)[:, None, None] * S + jnp.einsum("htk,htv->hkv", k_out, d, precision=_HI)
        return S, o

    S, o = lax.scan(step, S, (u, w, qk, q_in, k_out, c_end))
    return jnp.moveaxis(o, 1, 2).reshape(T, H, Dv), S


class Qwen3NextModel(LlamaModel):
    config: Qwen3NextConfig

    # -------------------------------------------------------------- params

    def init(self, rng: jax.Array) -> Dict[str, Any]:
        """Three stacks, because linear and full layers do not stack into
        one: ``linear`` [L_lin, ...], ``full`` [L_full, ...] and ``moe``
        [L, ...] (the expert block every layer ends in, with both block
        norms).  Norm scales start as published: zero where the norm is
        zero-centred, one for the Gated DeltaNet's output norm."""
        cfg = self.config
        E, V, pd = cfg.dim, cfg.padded_vocab, cfg.param_dtype
        kinds = cfg.layer_kinds
        L, Ll, Lf = len(kinds), kinds.count("linear"), kinds.count("full")
        H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        Hv, v_dim, X = cfg.lin_value_heads, cfg.lin_value_heads * cfg.lin_value_dim, cfg.n_experts
        k = iter(jax.random.split(rng, 24))
        std, out_std = 0.02, 0.02 / math.sqrt(2 * L)

        def norm(shape, s=std):
            return (jax.random.normal(next(k), shape) * s).astype(pd)

        # A = exp(A_log) uniform in (0, 16]; dt = softplus(dt_bias) log-uniform in [0.001, 0.1]
        dt = jnp.exp(jax.random.uniform(next(k), (Ll, Hv)) * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        linear = {
            "w_qkvz": norm((Ll, E, cfg.conv_dim + v_dim)),
            "w_ba": norm((Ll, E, 2 * Hv)),
            "conv_w": jax.random.uniform(next(k), (Ll, cfg.conv_kernel, cfg.conv_dim), minval=-0.5, maxval=0.5).astype(pd),
            "A_log": jnp.log(jax.random.uniform(next(k), (Ll, Hv), minval=1e-3, maxval=16.0)).astype(pd),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
            "out_norm": jnp.ones((Ll, cfg.lin_value_dim), pd),
            "w_out": norm((Ll, v_dim, E), out_std),
        }
        full = {
            "wq": norm((Lf, E, H * 2 * D)),
            "wk": norm((Lf, E, KV * D)),
            "wv": norm((Lf, E, KV * D)),
            "q_norm": jnp.zeros((Lf, D), pd),
            "k_norm": jnp.zeros((Lf, D), pd),
            "wo": norm((Lf, H * D, E), out_std),
        }
        moe = {
            "attn_norm": jnp.zeros((L, E), pd),
            "ffn_norm": jnp.zeros((L, E), pd),
            "router": norm((L, E, cfg.n_routed_experts)),
            "w_gate": norm((L, X, E, cfg.hidden_dim)),
            "w_up": norm((L, X, E, cfg.hidden_dim)),
            "w_down": norm((L, X, cfg.hidden_dim, E), out_std),
            "shared_gate": norm((L, E, 1)),
            "ws_gate": norm((L, E, cfg.shared_hidden_dim)),
            "ws_up": norm((L, E, cfg.shared_hidden_dim)),
            "ws_down": norm((L, cfg.shared_hidden_dim, E), out_std),
        }
        return {
            "tok_emb": norm((V, E)), "out_head": norm((E, V)), "final_norm": jnp.zeros((E,), pd),
            "linear": linear, "full": full, "moe": moe,
        }

    def param_pspecs(self, mesh=None) -> Dict[str, Any]:
        """Experts and vocabulary over tp; the mixers, router and shared
        expert whole on every device (2 KV heads do not divide over 4)."""
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        specs = jax.tree.map(lambda a: P(*([None] * a.ndim)), shapes)
        for name in ("w_gate", "w_up", "w_down"):
            specs["moe"][name] = P(None, "tp", None, None)
        specs["tok_emb"], specs["out_head"] = P("tp", None), P(None, "tp")
        return specs

    # --------------------------------------------------------------- pool

    def init_pages(self, num_pages: int, page_size: int, num_slots: int = 0) -> Tuple:
        """The pool (``pool_roles`` names its members): K/V pages of the FULL
        layers only [L_full, NP, PS, KV, D], the routing counter over the
        router's experts [n_routed_experts] int32, and per slot the Gated
        DeltaNet layers' recurrent state [L_lin, slots, Hv, Dk, Dv] float32
        and conv window [L_lin, slots, k - 1, conv_dim], and last the count
        of (layer, expert) weight reads that the routed layer's touched form
        made (int32, wrapping; ``parallel/moe.py dropless_moe_ffn``: a decode
        step of few slots reads the held experts its live rows chose, and a
        program that takes the masked form leaves the count as it was).
        Admission stays one number, pages: a slot's state is there whether
        it is used or not."""
        cfg = self.config
        if num_slots <= 0:
            raise ValueError("a model with per-slot state must be told the number of slots")
        kinds = cfg.layer_kinds
        Ll = kinds.count("linear")
        shape = (kinds.count("full"), num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
        return (
            jnp.zeros(shape, cfg.compute_dtype),
            jnp.zeros(shape, cfg.compute_dtype),
            jnp.zeros((cfg.n_routed_experts,), jnp.int32),
            jnp.zeros((Ll, num_slots, cfg.lin_value_heads, cfg.lin_key_dim, cfg.lin_value_dim), jnp.float32),
            jnp.zeros((Ll, num_slots, cfg.conv_kernel - 1, cfg.conv_dim), cfg.compute_dtype),
            jnp.zeros((), jnp.int32),
        )

    def pool_pspecs(self) -> Tuple:
        # nothing of the mixers is split, so neither is what they keep
        return (P(), P(), P(), P(), P(), P())

    def pool_roles(self) -> Tuple[str, ...]:
        return ("pages", "pages", "counter", "state", "state", "expert_reads")

    def held_experts(self) -> slice:
        cfg = self.config
        return slice(cfg.expert_offset, cfg.expert_offset + cfg.n_experts)

    # ------------------------------------------------------------- forward

    def apply(self, params, tokens, mesh=None):
        raise NotImplementedError("Qwen3NextModel has the serving path only (the paged programs)")

    def _gated_attn(self, x, mp, fp, fi: int, pages, wpage, woff, tables, q_pos, q_valid, n_blocks):
        """Full-attention mixer ``fi`` (its index among the full layers, and
        in the K/V pool): per-head zero-centred QK-norm, partial rotary, the
        shared page write and walk, then the output gate."""
        cfg = self.config
        cd = cfg.compute_dtype
        B, S, _ = x.shape
        H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        kp, vp = pages
        h = _zrms_norm(x, mp["attn_norm"], cfg.norm_eps).astype(cd)
        qg = (h @ fp["wq"].astype(cd)).reshape(B, S, H, 2, D)
        q, gate = qg[..., 0, :], qg[..., 1, :].reshape(B, S, H * D)
        k = (h @ fp["wk"].astype(cd)).reshape(B, S, KV, D)
        v = (h @ fp["wv"].astype(cd)).reshape(B, S, KV, D)
        q = _partial_rope(_zrms_norm(q, fp["q_norm"], cfg.norm_eps).astype(cd), q_pos, cfg.rope_theta, cfg.rotary_dim)
        k = _partial_rope(_zrms_norm(k, fp["k_norm"], cfg.norm_eps).astype(cd), q_pos, cfg.rope_theta, cfg.rotary_dim)
        kp = self._paged_write(kp, fi, wpage, woff, k.reshape(-1, KV, D))
        vp = self._paged_write(vp, fi, wpage, woff, v.reshape(-1, KV, D))
        attn = self._paged_attend(q, kp, vp, fi, tables, q_pos, q_valid, n_blocks)
        gated = (attn.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(cd)
        return x + gated @ fp["wo"].astype(cd), (kp, vp)

    def _gdn(self, x, mp, lp, li: int, state, conv, slot, q_pos, q_valid):
        """Gated DeltaNet mixer ``li`` (its index among the linear layers,
        and in the per-slot state).  x [B, S, E]: a decode step (B = slots,
        S = 1, ``slot`` None: row b is slot b) or a prefill chunk (B = 1, S
        = chunk, of slot ``slot``).  A row that begins a sequence (valid, at
        position 0) starts from a zero state and window; rows that are not
        valid (an inactive slot, a chunk's padded tail) leave both as they
        were."""
        cfg = self.config
        cd = cfg.compute_dtype
        B, S, _ = x.shape
        if S != 1 and S % GDN_BLOCK:
            raise ValueError(f"a prefill chunk of {S} rows is not a multiple of the scan's block of {GDN_BLOCK}")
        Hk, Hv, Dk, Dv = cfg.lin_key_heads, cfg.lin_value_heads, cfg.lin_key_dim, cfg.lin_value_dim
        f32 = jnp.float32

        h = _zrms_norm(x, mp["attn_norm"], cfg.norm_eps).astype(cd)
        qkvz = h @ lp["w_qkvz"].astype(cd)
        mixed, z = qkvz[..., : cfg.conv_dim], qkvz[..., cfg.conv_dim :]
        ba = (h @ lp["w_ba"].astype(cd)).astype(f32)
        # a row that is not valid neither decays the state (g = 0) nor writes to it (beta = 0)
        beta = jax.nn.sigmoid(ba[..., :Hv]) * q_valid[..., None]
        g = -jnp.exp(lp["A_log"].astype(f32)) * jax.nn.softplus(ba[..., Hv:] + lp["dt_bias"].astype(f32)) * q_valid[..., None]

        if slot is None:
            win, S_in = conv[li], state[li]
        else:
            win, S_in = lax.dynamic_index_in_dim(conv[li], slot, 0), lax.dynamic_index_in_dim(state[li], slot, 0)
        fresh = (q_valid[:, 0] & (q_pos[:, 0] == 0))[:, None, None]
        win = jnp.where(fresh, jnp.zeros_like(win), win)
        S_in = jnp.where(fresh[..., None], jnp.zeros_like(S_in), S_in)

        # causal depthwise conv over the last k-1 inputs and the call's own
        seq = jnp.concatenate([win, mixed], axis=1)  # [B, k-1 + S, channels]
        w = lp["conv_w"].astype(f32)
        y = sum(seq[:, j : j + S].astype(f32) * w[j] for j in range(cfg.conv_kernel))
        y = jax.nn.silu(y)
        # the window after the call: the k-1 inputs that end at each row's last valid one
        keep = q_valid.sum(-1)[:, None] + jnp.arange(cfg.conv_kernel - 1)[None]  # [B, k-1]
        win = jnp.take_along_axis(seq, keep[..., None], axis=1)

        kq = Hk * Dk
        rep = Hv // Hk
        q = jnp.repeat(_l2_norm(y[..., :kq].reshape(B, S, Hk, Dk)) * Dk**-0.5, rep, axis=2)
        k = jnp.repeat(_l2_norm(y[..., kq : 2 * kq].reshape(B, S, Hk, Dk)), rep, axis=2)
        v = y[..., 2 * kq :].reshape(B, S, Hv, Dv)
        if S == 1:
            o, S_out = jax.vmap(gated_delta_step)(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], S_in)
            o = o[:, None]
        else:
            o, S_out = jax.vmap(gated_delta_chunked)(q, k, v, g, beta, S_in)

        if slot is None:
            state, conv = state.at[li].set(S_out), conv.at[li].set(win)
        else:
            state, conv = state.at[li, slot].set(S_out[0]), conv.at[li, slot].set(win[0])
        # a plain-scale RMSNorm per head, then the gate
        o = o * lax.rsqrt((o**2).mean(-1, keepdims=True) + cfg.norm_eps) * lp["out_norm"].astype(f32)
        o = (o * jax.nn.silu(z.reshape(B, S, Hv, Dv).astype(f32))).astype(cd).reshape(B, S, Hv * Dv)
        return x + o @ lp["w_out"].astype(cd), state, conv

    def _ffn(self, x, mp):
        """The block every layer ends in: x [B, S, E] -> (x + the held
        experts' part of the routed sum + the gated shared expert, chosen
        [B, S, K] over the router's experts).  ``mp`` is the layer's slice
        of ``params["moe"]`` but for the three expert stacks, which are whole
        with the layer's index under "layer" (the routed layer takes one
        expert of them where it reads), and under "valid" which of x's rows
        are live [B, S]: a decode step's idle slots touch no expert."""
        from ray_tpu.parallel.moe import dropless_moe_ffn

        cfg = self.config
        cd = cfg.compute_dtype
        B, S, E = x.shape
        h = _zrms_norm(x, mp["ffn_norm"], cfg.norm_eps).astype(cd).reshape(B * S, E)
        with jax.named_scope("moe_ffn"):
            y, chosen = dropless_moe_ffn(
                h, mp["router"], mp["w_gate"], mp["w_up"], mp["w_down"], top_k=cfg.n_experts_per_tok,
                renormalize=cfg.norm_topk_prob, expert_offset=cfg.expert_offset, valid=mp["valid"].reshape(B * S), layer=mp["layer"],
            )
        with jax.named_scope("shared_expert"):
            shared = (jax.nn.silu(h @ mp["ws_gate"].astype(cd)) * (h @ mp["ws_up"].astype(cd))) @ mp["ws_down"].astype(cd)
            share = jax.nn.sigmoid((h @ mp["shared_gate"].astype(cd)).astype(jnp.float32))
            y = y + (shared.astype(jnp.float32) * share).astype(cd)
        return x + y.reshape(B, S, E), chosen.reshape(B, S, -1)

    def _paged_forward(self, params, x, pages, wpage, woff, tables, q_pos, q_valid, slot=None):
        from ray_tpu.parallel.moe import reads_touched_experts_only

        cfg = self.config
        kp, vp, load, state, conv, reads = pages
        tables, n_blocks = self._walk_blocks(tables, kp.shape[2], q_pos, q_valid)
        # the experts' stacks go to the routed layer whole: a layer's slice handed to its loop would be copied first
        experts = {name: params["moe"][name] for name in ("w_gate", "w_up", "w_down")}
        rest = {name: p for name, p in params["moe"].items() if name not in experts}
        touched_only = reads_touched_experts_only(x.shape[0] * x.shape[1], cfg.n_experts_per_tok, cfg.n_routed_experts)
        n_full = n_lin = 0
        for i, kind in enumerate(cfg.layer_kinds):
            mp = jax.tree.map(lambda p: p[i], rest)
            if kind == "full":
                fp = jax.tree.map(lambda p: p[n_full], params["full"])
                with jax.named_scope("gated_attn"):
                    x, (kp, vp) = self._gated_attn(x, mp, fp, n_full, (kp, vp), wpage, woff, tables, q_pos, q_valid, n_blocks)
                n_full += 1
            else:
                lp = jax.tree.map(lambda p: p[n_lin], params["linear"])
                with jax.named_scope("gdn"):
                    x, state, conv = self._gdn(x, mp, lp, n_lin, state, conv, slot, q_pos, q_valid)
                n_lin += 1
            x, chosen = self._ffn(x, {**mp, **experts, "layer": i, "valid": q_valid})
            hits = (jax.nn.one_hot(chosen, cfg.n_routed_experts, dtype=jnp.int32) * q_valid[..., None, None]).sum((0, 1, 2))
            load = load + hits
            if touched_only:  # the routed layer visited the held experts a live row chose, and read no other
                reads = reads + (hits[self.held_experts()] > 0).sum()
        x = _zrms_norm(x, params["final_norm"], cfg.norm_eps)
        return x.astype(cfg.compute_dtype), (kp, vp, load, state, conv, reads)
