"""LFM2's expert models (``model_type: lfm2_moe``; LFM2-24B-A2B; the dense
members of the family are published in transformers' ``modeling_lfm2.py``,
the expert block as ``Lfm2MoeSparseMoeBlock``), serving path: a decoder whose
mixer is a GATED SHORT CONVOLUTION in most layers and grouped-query attention
in the rest (``layer_types``), and whose FFN is a dense SwiGLU in the leading
``n_dense_layers`` and routed experts under a sigmoid router with a selection
bias after them.  The two kinds vary independently.

    x += mixer(norm(x));  x += ffn(norm(x))        norm: w * rmsnorm(x), eps 1e-5

The conv mixer, for a row h at position t (E = ``dim``, k = ``conv_kernel``):

    [B | C | u] = h W_in                          three blocks of E, in that order
    z = B * u;   c(t) = sum_j w_j * z(t - (k-1) + j)    depthwise, causal, z = 0 before the sequence, no bias, no activation
    y = (C * c) W_out

WHAT A SLOT KEEPS of a conv layer is ``[z(t-k+1) .. z(t-1)]``: k - 1 rows of
E (two rows of 2048 for LFM2-24B-A2B) and nothing that grows with t.  No conv
layer has pages and no attention layer a window: the pool is K/V pages for
the attending layers only, the routing counter, and ONE state member, the
windows [L_conv, slots, k - 1, E] in the compute type (``init_pages``).

The attention mixer: q, k, v projections without bias, an RMSNorm over each
head's ``head_dim`` on q and k BEFORE rotary ("rotate_half" pairs (i, i +
D/2) over all of D), the shared page write and blockwise walk.  The expert
FFN is ``parallel/moe.py dropless_moe_ffn`` told what the published block
does: score by sigmoid, choose by score + ``expert_bias``, weigh by the
scores themselves over (their sum + 1e-6), no shared expert.  The head is the
embedding, transposed.

``Lfm2MoeModel`` is a ``LlamaModel``: the engine's two paged programs, the
page write, the walk, the greedy sampler are that class's; the window through
the pool is ``llama.py``'s ``conv_window_taps`` / ``conv_window_after``, which
Jamba's mixer calls too.  What this file adds is the conv mixer, the per-head
QK-norm, ``_paged_forward`` over two kinds of mixer and two kinds of FFN, the
tied head and the pool.
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
from ray_tpu.models.qwen3_next import _partial_rope

# the published block divides a row's chosen scores by (their sum + 1e-6); DeepSeek-V3's by (their sum + 1e-20)
RENORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig(LlamaConfig):
    """Defaults are the published sizes of LFM2-24B-A2B.  ``hidden_dim`` is
    one routed expert's width, ``dense_hidden_dim`` the width of the leading
    ``n_dense_layers`` layers' FFN.  ``layer_types`` is the published list
    ("conv" / "full_attention" a layer); a model of ``n_layers`` layers is
    its first ``n_layers`` entries."""

    vocab_size: int = 65536
    dim: int = 2048
    n_layers: int = 40
    n_heads: int = 32
    n_kv_heads: int = 8
    hidden_dim: int = 1536
    max_seq_len: int = 8192
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    n_experts: int = 64
    n_experts_per_tok: int = 4
    dense_hidden_dim: int = 11776
    n_dense_layers: int = 2
    conv_kernel: int = 3
    layer_types: Tuple[str, ...] = tuple("full_attention" if i % 4 == 2 else "conv" for i in range(40))
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0

    def __post_init__(self):
        super().__post_init__()
        if len(self.layer_types) < self.n_layers or set(self.layer_types) - {"conv", "full_attention"}:
            raise ValueError(f"layer_types names {len(self.layer_types)} layers for n_layers={self.n_layers}; each is 'conv' or 'full_attention'")
        if self.n_heads % self.n_kv_heads or self.head_dim % 2 or not 0 <= self.n_dense_layers <= self.n_layers or self.conv_kernel < 2:
            raise ValueError("query heads are a multiple of KV heads, rotary dimensions pair up, the dense layers lead, a window holds at least one row")

    def build_model(self) -> "Lfm2MoeModel":
        return Lfm2MoeModel(self)

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        """"conv" or "attn" for each of the ``n_layers`` layers, in order."""
        return tuple("attn" if t == "full_attention" else "conv" for t in self.layer_types[: self.n_layers])

    def _layer_params(self) -> Dict[str, int]:
        """Parameters of one layer's parts, from the published keys."""
        E, kv = self.dim, self.n_kv_heads * self.head_dim
        return {
            "conv": E * 3 * E + E * E + E * self.conv_kernel,  # in_proj, out_proj, the taps
            "attn": 2 * E * E + 2 * E * kv + 2 * self.head_dim,  # q, out; k, v; the two head norms
            "dense": 3 * E * self.dense_hidden_dim + 2 * E,  # and the two block norms
            "outside_experts": E * self.n_experts + self.n_experts + 2 * E,  # router, its selection bias, the two block norms
            "expert": 3 * E * self.hidden_dim,
        }

    def num_params(self) -> int:
        """As published: the embedding is the head too and counts once."""
        n = self._layer_params()
        sparse = self.n_layers - self.n_dense_layers
        return int(self.vocab_size * self.dim + self.dim + sum(n[k] for k in self.layer_kinds) + self.n_dense_layers * n["dense"]
                   + sparse * (n["outside_experts"] + self.n_experts * n["expert"]))

    def active_params_per_token(self) -> int:
        idle = (self.n_layers - self.n_dense_layers) * (self.n_experts - self.n_experts_per_tok)
        return int(self.num_params() - idle * self._layer_params()["expert"])


class Lfm2MoeModel(LlamaModel):
    config: Lfm2MoeConfig

    # -------------------------------------------------------------- params

    def init(self, rng: jax.Array) -> Dict[str, Any]:
        """Four stacks, because the mixer's kind and the FFN's kind vary
        independently and neither pair stacks into one: ``conv`` [L_conv,
        ...] and ``attn`` [L_attn, ...] (the mixers), ``dense``
        [n_dense_layers, ...] and ``moe`` [the rest, ...] (the FFNs, each
        with both block norms of its layer); no ``out_head``.  Every matrix
        N(0, 0.02), the taps too (the family's initial value for a Conv1d);
        the selection bias is drawn, not zero (the published initial value):
        N(0, 0.2), the spread of the sigmoid scores themselves at these
        weights, so that it decides choices."""
        cfg = self.config
        E, V, pd, D = cfg.dim, cfg.padded_vocab, cfg.param_dtype, cfg.head_dim
        kinds = cfg.layer_kinds
        L, Lc, La = len(kinds), kinds.count("conv"), kinds.count("attn")
        Ld, Lm, X, kv = cfg.n_dense_layers, L - cfg.n_dense_layers, cfg.n_experts, cfg.n_kv_heads * D
        k = iter(jax.random.split(rng, 20))
        std, out_std = 0.02, 0.02 / math.sqrt(2 * L)

        def norm(shape, s=std):
            return (jax.random.normal(next(k), shape) * s).astype(pd)

        conv = {"w_in": norm((Lc, E, 3 * E)), "conv_w": norm((Lc, cfg.conv_kernel, E)), "w_out": norm((Lc, E, E), out_std)}
        attn = {
            "wq": norm((La, E, E)), "wk": norm((La, E, kv)), "wv": norm((La, E, kv)), "wo": norm((La, E, E), out_std),
            "q_norm": jnp.ones((La, D), pd), "k_norm": jnp.ones((La, D), pd),
        }
        dense = {
            "op_norm": jnp.ones((Ld, E), pd), "ffn_norm": jnp.ones((Ld, E), pd),
            "w_gate": norm((Ld, E, cfg.dense_hidden_dim)), "w_up": norm((Ld, E, cfg.dense_hidden_dim)),
            "w_down": norm((Ld, cfg.dense_hidden_dim, E), out_std),
        }
        moe = {
            "op_norm": jnp.ones((Lm, E), pd), "ffn_norm": jnp.ones((Lm, E), pd),
            "router": norm((Lm, E, X)), "router_bias": norm((Lm, X), 0.2),
            "w_gate": norm((Lm, X, E, cfg.hidden_dim)), "w_up": norm((Lm, X, E, cfg.hidden_dim)),
            "w_down": norm((Lm, X, cfg.hidden_dim, E), out_std),
        }
        return {"tok_emb": norm((V, E)), "final_norm": jnp.ones((E,), pd), "conv": conv, "attn": attn, "dense": dense, "moe": moe}

    def param_pspecs(self, mesh=None) -> Dict[str, Any]:
        """Experts over tp; the mixers, the dense layers, the router and the
        tied matrix whole on every device."""
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        specs = jax.tree.map(lambda a: P(*([None] * a.ndim)), shapes)
        for name in ("w_gate", "w_up", "w_down"):
            specs["moe"][name] = P(None, "tp", None, None)
        return specs

    # --------------------------------------------------------------- pool

    def init_pages(self, num_pages: int, page_size: int, num_slots: int = 0) -> Tuple:
        """The pool (``pool_roles`` names its members): K/V pages of the
        ATTENDING layers only [L_attn, NP, PS, KV * D], the routing counter
        [n_experts] int32, and per slot the conv layers' windows [L_conv,
        slots, k - 1, E] in the compute type: the last k - 1 values of z = B
        * u.  There is no recurrent state beside a window; a slot's windows
        are 64 KiB at the published widths and ten layers.

        A position's KV heads lie in ONE row of KV * D values (512: four lane
        tiles).  As [.., PS, KV, D] with D = 64, half a lane tile, the TPU
        lays a pages member out with the PAGE axis minor-most, and both
        programs copy the pool into row-major order and back on every call
        (4 GB of temporaries at 30 720 pages: out of memory; the same
        finding as ``DeepseekV3Config.cache_row_dim``).  The walk reshapes a
        gathered block to heads (``_paged_attend``); nothing else reads it."""
        cfg = self.config
        if num_slots <= 0:
            raise ValueError("a model with per-slot state must be told the number of slots")
        kinds = cfg.layer_kinds
        shape = (kinds.count("attn"), num_pages, page_size, cfg.n_kv_heads * cfg.head_dim)
        return (
            jnp.zeros(shape, cfg.compute_dtype),
            jnp.zeros(shape, cfg.compute_dtype),
            jnp.zeros((cfg.n_experts,), jnp.int32),
            jnp.zeros((kinds.count("conv"), num_slots, cfg.conv_kernel - 1, cfg.dim), cfg.compute_dtype),
        )

    def pool_pspecs(self) -> Tuple:
        page = P(None, None, None, "tp")  # a row is its KV heads one after the other
        return (page, page, P(), P())

    def pool_roles(self) -> Tuple[str, ...]:
        return ("pages", "pages", "counter", "state")

    # ------------------------------------------------------------- forward

    def apply(self, params, tokens, mesh=None):
        raise NotImplementedError("Lfm2MoeModel has the serving path only (the paged programs)")

    def _logits(self, params, x):
        """The tied head: contracted against the embedding where it lies
        ([V, E], no transposed twin)."""
        return jnp.einsum("...e,ve->...v", x, params["tok_emb"].astype(self.config.compute_dtype))

    def _in_proj(self, h, cp):
        """Normed rows h [B, S, E] -> the conv mixer's (B, C, u), each [B, S,
        E]: the three blocks of ``in_proj``'s columns, in that order."""
        E = self.config.dim
        bcu = h @ cp["w_in"].astype(self.config.compute_dtype)
        return bcu[..., :E], bcu[..., E : 2 * E], bcu[..., 2 * E :]

    def _short_conv(self, h, cp, ci: int, windows, slot, q_pos, q_valid):
        """Conv mixer ``ci`` (its index among the conv layers, and in the
        windows) on normed rows h [B, S, E]: a decode step (B = slots, S = 1,
        ``slot`` None: row b is slot b) or a prefill chunk (B = 1, of slot
        ``slot``).  A row that begins a sequence (valid, at position 0)
        starts from a zero window, so a reused slot starts clean; rows that
        are not valid (an inactive slot, a chunk's padded tail) leave the
        window as it was.  Returns (what the mixer adds to x, windows)."""
        cd = self.config.compute_dtype
        B, C, u = self._in_proj(h, cp)
        with jax.named_scope("conv_window"):
            c, seq, _ = conv_window_taps(windows, ci, slot, B * u, cp["conv_w"], q_pos, q_valid)
            windows = conv_window_after(windows, ci, slot, seq, q_valid)
        y = (C.astype(jnp.float32) * c).astype(cd)
        return y @ cp["w_out"].astype(cd), windows

    def _head_norm_rope(self, x, w, positions):
        """x [B, S, heads, D] -> each head through its RMSNorm (scale w [D]),
        THEN rotary over all of D ("rotate_half" pairs)."""
        cfg = self.config
        x = _rms_norm(x, w.astype(jnp.float32), cfg.norm_eps).astype(cfg.compute_dtype)
        return _partial_rope(x, positions, cfg.rope_theta, cfg.head_dim)

    def _attn(self, h, ap, ai: int, pages, wpage, woff, tables, q_pos, q_valid, n_blocks):
        """Attention mixer ``ai`` (its index among the attending layers, and
        in the K/V pool) on normed rows h: per-head RMSNorm on q and k, then
        rotary over the whole head, the shared page write and walk.  Returns
        (what the mixer adds to x, (kp, vp))."""
        cfg = self.config
        cd = cfg.compute_dtype
        B, S, _ = h.shape
        H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        kp, vp = pages
        # behind the barrier the three stay plain 2-D matmuls that read their weights where they lie (LlamaModel._qkv)
        q, k, v = lax.optimization_barrier((h @ ap["wq"].astype(cd), h @ ap["wk"].astype(cd), h @ ap["wv"].astype(cd)))
        q = self._head_norm_rope(q.reshape(B, S, H, D), ap["q_norm"], q_pos)
        k = self._head_norm_rope(k.reshape(B, S, KV, D), ap["k_norm"], q_pos)
        kp = self._paged_write(kp, ai, wpage, woff, k.reshape(-1, KV * D))
        vp = self._paged_write(vp, ai, wpage, woff, v.reshape(-1, KV * D))
        attn = self._paged_attend(q, kp, vp, ai, tables, q_pos, q_valid, n_blocks)
        return attn @ ap["wo"].astype(cd), (kp, vp)

    def _dense_ffn(self, x, fp):
        """A leading layer's FFN: one SwiGLU of ``dense_hidden_dim``."""
        cfg = self.config
        cd = cfg.compute_dtype
        h = _rms_norm(x, fp["ffn_norm"].astype(jnp.float32), cfg.norm_eps).astype(cd)
        return x + (jax.nn.silu(h @ fp["w_gate"].astype(cd)) * (h @ fp["w_up"].astype(cd))) @ fp["w_down"].astype(cd)

    def _ffn(self, x, mp):
        """An expert layer's FFN: x [B, S, E] -> (x + the routed experts'
        weighted sum; chosen [B, S, K]).  No shared expert."""
        from ray_tpu.parallel.moe import dropless_moe_ffn

        cfg = self.config
        B, S, E = x.shape
        h = _rms_norm(x, mp["ffn_norm"].astype(jnp.float32), cfg.norm_eps).astype(cfg.compute_dtype).reshape(B * S, E)
        with jax.named_scope("moe_ffn"):
            y, chosen = dropless_moe_ffn(
                h, mp["router"], mp["w_gate"], mp["w_up"], mp["w_down"], top_k=cfg.n_experts_per_tok,
                renormalize=cfg.norm_topk_prob, scoring="sigmoid", bias=mp["router_bias"], scale=cfg.routed_scaling_factor, renorm_eps=RENORM_EPS,
            )
        return x + y.reshape(B, S, E), chosen.reshape(B, S, -1)

    def _paged_forward(self, params, x, pages, wpage, woff, tables, q_pos, q_valid, slot=None):
        cfg = self.config
        cd = cfg.compute_dtype
        kp, vp, load, windows = pages
        tables, n_blocks = self._walk_blocks(tables, kp.shape[2], q_pos, q_valid)
        n_attn = n_conv = 0
        for i, kind in enumerate(cfg.layer_kinds):
            dense = i < cfg.n_dense_layers
            fp = jax.tree.map(lambda p: p[i if dense else i - cfg.n_dense_layers], params["dense" if dense else "moe"])
            h = _rms_norm(x, fp["op_norm"].astype(jnp.float32), cfg.norm_eps).astype(cd)
            if kind == "attn":
                ap = jax.tree.map(lambda p: p[n_attn], params["attn"])
                with jax.named_scope("attn"):
                    y, (kp, vp) = self._attn(h, ap, n_attn, (kp, vp), wpage, woff, tables, q_pos, q_valid, n_blocks)
                n_attn += 1
            else:
                cp = jax.tree.map(lambda p: p[n_conv], params["conv"])
                with jax.named_scope("short_conv"):
                    y, windows = self._short_conv(h, cp, n_conv, windows, slot, q_pos, q_valid)
                n_conv += 1
            x = x + y
            if dense:
                x = self._dense_ffn(x, fp)
                continue
            x, chosen = self._ffn(x, fp)
            hits = jax.nn.one_hot(chosen, cfg.n_experts, dtype=jnp.int32) * q_valid[..., None, None]
            load = load + hits.sum((0, 1, 2))
        x = _rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
        return x.astype(cd), (kp, vp, load, windows)
