"""Llama family, TPU-first: RMSNorm + RoPE + grouped-query attention, with a
dense SwiGLU FFN (Llama, Mistral) or -- ``LlamaConfig.n_experts`` -- a routed
layer of SwiGLU experts, dropless top-k with a float32 router
(``parallel/moe.py dropless_moe_ffn``), and optionally RMSNorm on the query
and key projections (``qk_norm``): together the OLMoE block.  Two forward
paths share one ``_qkv`` and one ``_ffn``: ``apply`` (training, and the plain
reference the engine's tests compare with) and the serving engine's paged
pair, ``prefill_chunk_paged`` / ``decode_step_paged``.

Same functional conventions as gpt2.py — pytree params with stacked
[n_layer, ...] leading dim, lax.scan + remat, bf16 compute, declarative
PartitionSpecs.

The reference ships no LM; its serve replicas wrap user torch modules
(reference: python/ray/serve/_private/replica.py:58).  Here the model is
first-party so a deployment is jit-compiled end to end.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    hidden_dim: int = 11008
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # sparse experts (OLMoE): n_experts > 0 replaces the dense FFN by
    # n_experts SwiGLU experts of width hidden_dim, n_experts_per_tok of
    # them a token, weighted by the router's softmax over ALL experts
    # (not renormalised over the chosen ones); qk_norm puts an RMSNorm
    # over the whole query and key projections, before the heads
    n_experts: int = 0
    n_experts_per_tok: int = 0
    qk_norm: bool = False

    def __post_init__(self):
        if self.n_experts and not 0 < self.n_experts_per_tok <= self.n_experts:
            raise ValueError(
                f"n_experts_per_tok={self.n_experts_per_tok} must lie in 1..n_experts={self.n_experts}"
            )

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    def build_model(self) -> "LlamaModel":
        """The model this configuration describes (``serve/llm.py``
        builds it from the configuration alone)."""
        return LlamaModel(self)

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab_size, 128)

    @classmethod
    def llama_3b(cls, **kw) -> "LlamaConfig":
        """~3.3B llama-family config sized for ONE 16G v5e chip in bf16
        (6.7 GB weights + KV cache headroom).  head_dim 128 keeps the
        attention MXU/lane aligned."""
        return cls(dim=3072, n_layers=26, n_heads=24, n_kv_heads=24, hidden_dim=8192, **kw)

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq_len", 64)
        return cls(dim=64, n_layers=2, n_heads=4, n_kv_heads=2, hidden_dim=128, **kw)

    def num_params(self) -> int:
        E, L, H, X = self.dim, self.n_layers, self.hidden_dim, self.n_experts
        kv_dim = self.n_kv_heads * self.head_dim
        ffn = X * (E + 3 * E * H) if X else 3 * E * H  # router + experts, or one SwiGLU
        qk = E + kv_dim if self.qk_norm else 0
        per_layer = 2 * E * E + 2 * E * kv_dim + ffn + 2 * E + qk
        return int(self.padded_vocab * E * 2 + L * per_layer + E)

    def active_params_per_token(self) -> int:
        """Parameters one token's forward pass multiplies by: all of them
        but the experts it was not routed to."""
        idle = max(0, self.n_experts - self.n_experts_per_tok)
        return int(self.num_params() - self.n_layers * idle * 3 * self.dim * self.hidden_dim)


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    norm = x32 * jax.lax.rsqrt((x32**2).mean(-1, keepdims=True) + eps)
    return norm * scale


def ctx_block_pages(pages_per_slot: int, page_size: int) -> int:
    """Pages in one context block of the paged programs' walk: 256
    positions (the chunk a prompt is prefilled in; coarser blocks walk past
    more dead context, finer ones pay more loop trips), or the whole table
    where that is shorter, in whole pages and never less than one."""
    return max(1, min(256, pages_per_slot * page_size) // page_size)


def _rope(x, positions, theta):
    # x: [..., seq, heads, head_dim]
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, d/2]
    cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.stack([out1, out2], axis=-1).reshape(x.shape).astype(x.dtype)


class LlamaModel:
    def __init__(self, config: LlamaConfig):
        self.config = config

    # -------------------------------------------------------------- params

    def init(self, rng: jax.Array) -> Dict[str, Any]:
        cfg = self.config
        E, L, V, H = cfg.dim, cfg.n_layers, cfg.padded_vocab, cfg.hidden_dim
        kv_dim = cfg.n_kv_heads * cfg.head_dim
        pd = cfg.param_dtype
        k = iter(jax.random.split(rng, 10))
        std = 0.02
        X = cfg.n_experts
        ffn_in, ffn_out = ((L, X, E, H), (L, X, H, E)) if X else ((L, E, H), (L, H, E))

        def norm(key, shape, s=std):
            return (jax.random.normal(key, shape) * s).astype(pd)

        # keys are drawn in the order the dense tree always drew them, so a
        # dense config's weights are what they were before experts existed
        tok_emb, out_head = norm(next(k), (V, E)), norm(next(k), (E, V))
        layers = {
            "attn_norm": jnp.ones((L, E), pd),
            "ffn_norm": jnp.ones((L, E), pd),
            "wq": norm(next(k), (L, E, E)),
            "wk": norm(next(k), (L, E, kv_dim)),
            "wv": norm(next(k), (L, E, kv_dim)),
            "wo": norm(next(k), (L, E, E), std / math.sqrt(2 * L)),
            "w_gate": norm(next(k), ffn_in),
            "w_up": norm(next(k), ffn_in),
            "w_down": norm(next(k), ffn_out, std / math.sqrt(2 * L)),
        }
        if X:
            layers["router"] = norm(next(k), (L, E, X))
        if cfg.qk_norm:
            layers["q_norm"] = jnp.ones((L, E), pd)
            layers["k_norm"] = jnp.ones((L, kv_dim), pd)
        return {"tok_emb": tok_emb, "out_head": out_head, "final_norm": jnp.ones((E,), pd), "layers": layers}

    def param_pspecs(self, mesh=None) -> Dict[str, Any]:
        # mesh accepted for interface parity with GPT2Model (whose pp path
        # re-layers the specs); llama pp integration rides the same pipeline
        # primitive when needed
        cfg = self.config
        layers = {
            "attn_norm": P("fsdp", None),
            "ffn_norm": P("fsdp", None),
            "wq": P("fsdp", None, "tp"),
            "wk": P("fsdp", None, "tp"),
            "wv": P("fsdp", None, "tp"),
            "wo": P("fsdp", "tp", None),
            "w_gate": P("fsdp", None, "tp"),
            "w_up": P("fsdp", None, "tp"),
            "w_down": P("fsdp", "tp", None),
        }
        if cfg.n_experts:
            # whole experts over tp (their expert dimension); the router is
            # small and every device needs all of it
            for name in ("w_gate", "w_up", "w_down"):
                layers[name] = P("fsdp", "tp", None, None)
            layers["router"] = P("fsdp", None, None)
        if cfg.qk_norm:
            # the norm is over the whole projection: its scale stays whole
            layers["q_norm"] = P("fsdp", None)
            layers["k_norm"] = P("fsdp", None)
        return {
            "tok_emb": P("tp", None),
            "out_head": P(None, "tp"),
            "final_norm": P(None),
            "layers": layers,
        }

    # ------------------------------------------------------------- forward

    def _qkv(self, x, lp, positions):
        """Normed input -> rotated queries and keys, values, split into
        heads.  With ``qk_norm`` the query and key projections pass through
        an RMSNorm over their whole width before the split (OLMoE)."""
        cfg = self.config
        cd = cfg.compute_dtype
        B, S, _ = x.shape
        H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        h = _rms_norm(x, lp["attn_norm"].astype(jnp.float32), cfg.norm_eps).astype(cd)
        q = h @ lp["wq"].astype(cd)
        k = h @ lp["wk"].astype(cd)
        if cfg.qk_norm:
            q = _rms_norm(q, lp["q_norm"].astype(jnp.float32), cfg.norm_eps).astype(cd)
            k = _rms_norm(k, lp["k_norm"].astype(jnp.float32), cfg.norm_eps).astype(cd)
        v = h @ lp["wv"].astype(cd)
        if not cfg.qk_norm:
            # Nothing stands between these matmuls and the split into heads,
            # and the TPU compiler would fold the split INTO them: it then
            # reads the weight as [heads, D, E], contraction axis minor,
            # which a row-major stack [L, E, heads * D] is not, and re-lays
            # all of wq/wk/wv on every call of either paged program (1.6 GB
            # moved, 2.3 ms at Mistral-7B's widths and 16 layers).  Behind
            # the barrier they stay the plain 2-D matmuls the FFN's are,
            # which read a layer's slice of the stack where it lies; the
            # split is a view of a few rows.  No value changes
            # (tests/test_weight_copies.py compiles both programs for the
            # v5e without a chip and holds this).
            q, k, v = jax.lax.optimization_barrier((q, k, v))
        v = v.reshape(B, S, KV, D)
        q = _rope(q.reshape(B, S, H, D), positions, cfg.rope_theta)
        k = _rope(k.reshape(B, S, KV, D), positions, cfg.rope_theta)
        return q, k, v

    def _ffn(self, x, lp):
        """The block's second half, x [B, S, E] -> (x + FFN(norm(x)),
        chosen): a dense SwiGLU (chosen None) or, with ``n_experts``, the
        dropless routed experts and each row's chosen experts [B, S, K]."""
        cfg = self.config
        cd = cfg.compute_dtype
        h = _rms_norm(x, lp["ffn_norm"].astype(jnp.float32), cfg.norm_eps).astype(cd)
        if not cfg.n_experts:
            gate = jax.nn.silu(h @ lp["w_gate"].astype(cd))
            up = h @ lp["w_up"].astype(cd)
            return x + (gate * up) @ lp["w_down"].astype(cd), None
        from ray_tpu.parallel.moe import dropless_moe_ffn

        B, S, E = x.shape
        with jax.named_scope("moe_ffn"):
            y, chosen = dropless_moe_ffn(
                h.reshape(B * S, E), lp["router"], lp["w_gate"], lp["w_up"], lp["w_down"],
                top_k=cfg.n_experts_per_tok,
            )
        return x + y.reshape(B, S, E), chosen.reshape(B, S, -1)

    def _layer(self, x, lp, positions, mesh=None):
        """The plain causal layer: x [B, S, E] over the whole sequence."""
        cfg = self.config
        cd = cfg.compute_dtype
        B, S, E = x.shape
        H, KV = cfg.n_heads, cfg.n_kv_heads

        q, k, v = self._qkv(x, lp, positions)
        # grouped-query: repeat kv heads up to H
        if KV != H:
            rep = H // KV
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        # the shared dispatch (splash pallas kernel on TPU, fused XLA
        # elsewhere — ops/attention.py)
        from ray_tpu.ops.attention import causal_attention

        attn = causal_attention(q, k, v, mesh=mesh).reshape(B, S, E)
        x, _ = self._ffn(x + attn @ lp["wo"].astype(cd), lp)
        return x

    def apply(self, params, tokens, mesh=None):
        """The whole-sequence forward: tokens [B, S] → logits [B, S, V] (bf16)."""
        cfg = self.config
        cd = cfg.compute_dtype
        B, S = tokens.shape
        x = params["tok_emb"].astype(cd)[tokens]
        positions = jnp.arange(S)

        def body(x, lp):
            layer = lambda x_, lp_: self._layer(x_, lp_, positions, mesh=mesh)
            if cfg.remat:
                layer = jax.checkpoint(layer)
            return layer(x, lp), None

        x, _ = jax.lax.scan(body, x, params["layers"])
        x = _rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps).astype(cd)
        return x @ params["out_head"].astype(cd)

    def loss(self, params, tokens, targets, mesh=None):
        cfg = self.config
        logits = self.apply(params, tokens, mesh).astype(jnp.float32)
        if cfg.padded_vocab != cfg.vocab_size:
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(pad_mask, -1e30, logits)
        label_logit = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        lse = jax.nn.logsumexp(logits, axis=-1)
        return (lse - label_logit).mean()

    # ------------------------------------------------- paged decode (engine)
    #
    # The continuous-batching engine (ray_tpu/serve/engine/) shares ONE
    # fixed-shape page pool across sequences of different lengths: physical
    # KV pages [L, num_pages, page_size, KV, D] plus a per-slot page table
    # mapping logical page -> physical page (-1 = unallocated).  Shapes
    # depend only on (num_slots, pages_per_slot, page_size), never on any
    # sequence's length — the jit-shape invariant that keeps a mixed-length
    # fleet on one compiled program (engine/DESIGN.md).
    #
    # Attention reads the pool a context BLOCK at a time
    # (``ctx_block_pages`` pages): a ``fori_loop`` whose trip count the
    # program computes from the positions it is given walks the page table
    # only as far as the longest live context of the call, gathers that
    # block's pages [B, block, KV, D] and folds them into a running
    # (online) softmax -- float32 maximum, denominator and accumulator.
    # Grouped query heads are contracted against their KV head in place
    # (q as [B, Q, KV, G, D]); K and V are never repeated to H heads.  A
    # block that is wholly masked for a row leaves that row's three
    # statistics bit for bit as they were, so a row's result does not depend
    # on how far the others made the walk go.  Plain XLA: the layout (page
    # pools + page indices + lengths) is the TPU paged-attention kernel's,
    # which can replace the gather without touching the bookkeeping.

    def _paged_write(self, buf, li: int, wpage, woff, vals):
        """Scatter one token per slot into layer ``li`` of a page pool.
        ``wpage`` rows for inactive/unallocated slots are out of range and
        dropped — a token-sized update on the full buffer, which is donated,
        so XLA writes it in place."""
        return buf.at[li, wpage, woff].set(vals.astype(buf.dtype), mode="drop")

    def _paged_attend(self, q, kp, vp, li: int, tables, q_pos, q_valid, n_blocks, *, value_dim=None, scale=None):
        """Causal attention of q [B, Q, H, D] over layer ``li`` of the pool,
        each row b through its page table cut into blocks, ``tables[b]``
        [n, pages a block] (-1 = no page); a query at logical position
        ``q_pos[b, q]`` sees positions 0..q_pos, ``q_valid`` [B, Q] masks
        idle rows (their result is finite and unused).  Walks blocks
        0..n_blocks-1 (a traced scalar, at least 1).  Returns [B, Q, H*D].

        What a model whose cache is not K and V heads of one width says
        (``models/deepseek_v3.py``: one latent row a position): ``vp`` None --
        a position's values are the first ``value_dim`` of its key row, so
        ONE gather of a block serves scores and values; ``scale`` in place
        of D**-0.5.  The result is then [B, Q, H*value_dim]."""
        cfg = self.config
        cd = cfg.compute_dtype
        B, Q, H, D = q.shape
        KV = cfg.n_kv_heads
        G = H // KV
        Dv = D if value_dim is None else value_dim
        qk_scale = D**-0.5 if scale is None else scale
        NP, PS = kp.shape[1], kp.shape[2]
        blk = tables.shape[2] * PS
        q = q.reshape(B, Q, KV, G, D)
        offs = jnp.arange(blk)

        def block(i, carry):
            m, l, acc = carry
            tab = jax.lax.dynamic_index_in_dim(tables, i, axis=1, keepdims=False)  # [B, pages]
            phys = jnp.clip(tab, 0, NP - 1)
            keys = kp[li, phys].reshape(B, blk, KV, D)
            vals = keys[..., :Dv] if vp is None else vp[li, phys].reshape(B, blk, KV, D)
            seen = (i * blk + offs)[None, None, :] <= q_pos[:, :, None]  # [B, Q, blk]
            valid = seen & q_valid[:, :, None] & jnp.repeat(tab >= 0, PS, axis=1)[:, None, :]
            s = jnp.einsum("bqkgd,btkd->bkgqt", q, keys, preferred_element_type=jnp.float32)
            s = jnp.where(valid[:, None, None], s * qk_scale, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            # a block masked for the whole row: m_new == m, so scale is
            # exp(0) = 1 and every p is exp(-1e30 - m) = 0 -- nothing moves
            scale = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * scale + p.sum(-1)
            pv = jnp.einsum(
                "bkgqt,btkd->bkgqd", p.astype(cd), vals, preferred_element_type=jnp.float32
            )
            return m_new, l, acc * scale[..., None] + pv

        stat = (B, KV, G, Q)
        init = (
            jnp.full(stat, -1e30, jnp.float32),
            jnp.zeros(stat, jnp.float32),
            jnp.zeros(stat + (Dv,), jnp.float32),
        )
        _, l, acc = jax.lax.fori_loop(0, n_blocks, block, init)
        # an idle row kept m = -1e30, so its p were exp(0) = 1: l > 0, finite
        out = (acc / l[..., None]).astype(cd)  # [B, KV, G, Q, Dv]
        return out.transpose(0, 3, 1, 2, 4).reshape(B, Q, H * Dv)

    def _paged_layer(self, x, lp, li, pages, wpage, woff, tables, q_pos, q_valid, n_blocks):
        """One transformer layer over paged KV: write this step's K/V into
        the pool, then attend over the pool through the page tables.
        x [B, S, E] (decode: B=slots,S=1; prefill chunk: B=1,S=chunk).
        ``pages`` is (k_pages, v_pages) and, for an expert model, the
        per-expert count of routed assignments [X] int32, to which this
        layer adds the choices of its ``q_valid`` [B, S] rows.  Returns
        (x, pages)."""
        cfg = self.config
        cd = cfg.compute_dtype
        KV, D = cfg.n_kv_heads, cfg.head_dim
        kp, vp, *load = pages

        q, k, v = self._qkv(x, lp, q_pos)

        kp = self._paged_write(kp, li, wpage, woff, k.reshape(-1, KV, D))
        vp = self._paged_write(vp, li, wpage, woff, v.reshape(-1, KV, D))
        attn = self._paged_attend(q, kp, vp, li, tables, q_pos, q_valid, n_blocks)
        x, chosen = self._ffn(x + attn @ lp["wo"].astype(cd), lp)
        if chosen is not None:
            hits = jax.nn.one_hot(chosen, cfg.n_experts, dtype=jnp.int32) * q_valid[..., None, None]
            load = [load[0] + hits.sum((0, 1, 2))]
        return x, (kp, vp, *load)

    def _walk_blocks(self, tables, page_size: int, q_pos, q_valid):
        """The page tables [B, MP] cut into the walk's blocks [B, n, pages a
        block] (a last block the table does not fill is padded with pages
        that do not exist), and how many of them the call's longest live
        position reaches."""
        B, MP = tables.shape
        bp = ctx_block_pages(MP, page_size)
        tables = jnp.pad(tables, ((0, 0), (0, -MP % bp)), constant_values=-1).reshape(B, -1, bp)
        longest = jnp.max(jnp.where(q_valid, q_pos, 0))
        return tables, jnp.minimum(longest // (bp * page_size) + 1, tables.shape[1])

    def _paged_forward(self, params, x, pages, wpage, woff, tables, q_pos, q_valid, slot=None):
        """The layers of both paged programs: x [B, S, E] at positions
        ``q_pos`` [B, S] through tables [B, MP] -> (normed x, pages).  The
        walk ends at the block of the call's longest live position.
        ``slot`` is the prefill chunk's slot (None in a decode step, whose
        rows are the slots): a model with per-slot state needs it, this one
        keeps everything in pages."""
        cfg = self.config
        tables, n_blocks = self._walk_blocks(tables, pages[0].shape[2], q_pos, q_valid)
        for li in range(cfg.n_layers):
            lp = jax.tree.map(lambda p: p[li], params["layers"])
            x, pages = self._paged_layer(
                x, lp, li, pages, wpage, woff, tables, q_pos, q_valid, n_blocks
            )
        x = _rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
        return x.astype(cfg.compute_dtype), pages

    def _logits(self, params, x):
        """Normed activations [..., E] -> logits over the padded vocabulary."""
        return x @ params["out_head"].astype(self.config.compute_dtype)

    def _sample_greedy(self, logits):
        """argmax with the vocab padding masked (a padded id must never
        enter a sequence — it has no embedding semantics)."""
        cfg = self.config
        if cfg.padded_vocab != cfg.vocab_size:
            pad = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(pad, -jnp.inf, logits)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def init_pages(self, num_pages: int, page_size: int, num_slots: int = 0) -> Tuple:
        """Physical KV page pool shared by every engine slot:
        [L, num_pages, page_size, KV, D] pair.  An expert model's pool
        carries a third member, the routing counter [n_experts] int32
        (assignments per expert, summed over layers and calls, wrapping):
        it rides through both programs with the pool, so the engine reads
        no further array per turn.

        The pool's contract with the engine, whatever the model: a tuple
        whose members the model names (``pool_roles``) and shards
        (``pool_pspecs``); the engine counts no positions."""
        cfg = self.config
        shape = (cfg.n_layers, num_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
        pool = (jnp.zeros(shape, cfg.compute_dtype), jnp.zeros(shape, cfg.compute_dtype))
        if cfg.n_experts:
            pool += (jnp.zeros((cfg.n_experts,), jnp.int32),)
        return pool

    def pool_pspecs(self) -> Tuple:
        """A PartitionSpec per member of ``init_pages``' pool: the pages
        over their KV heads (tp), the routing counter whole."""
        page = P(None, None, None, "tp", None)
        return (page, page) + ((P(),) if self.config.n_experts else ())

    def pool_roles(self) -> Tuple[str, ...]:
        """What each member of ``init_pages``' pool is to the engine:
        "pages" (indexed by physical page on axis 1: ``defrag`` moves
        them), "counter" (the routing counter, at most one), "state"
        (per-SLOT tensors, ``num_slots`` wide, that no page move touches;
        this model has none) or "expert_reads" (``models/qwen3_next.py``: a
        scalar count of the expert weight reads its decode steps made)."""
        return ("pages", "pages") + (("counter",) if self.config.n_experts else ())

    def held_experts(self) -> slice:
        """Which of the routing counter's experts this model holds: all."""
        return slice(0, self.config.n_experts)

    def decode_step_paged(
        self, params, pages, tables, tokens, positions, active, page_size: int
    ):
        """One engine iteration: decode one token for every active slot.

        pages: (k_pages, v_pages) [L, NP, PS, KV, D] (and an expert
        model's routing counter, ``init_pages``); tables [S, MP] int32
        (physical page per logical page, -1 unallocated); tokens [S] int32
        (the token each slot feeds); positions [S] int32 (cache index the
        fed token is written at); active [S] bool.  Returns
        (next_tokens [S] int32 — greedy, device-argmaxed so only S ints
        cross to the host per step — and the updated pool)."""
        cd = self.config.compute_dtype
        NP = pages[0].shape[1]

        x = params["tok_emb"].astype(cd)[tokens][:, None, :]  # [S, 1, E]
        # write target: one pool row per slot; inactive or table-miss rows
        # go out of range and are dropped by the scatter
        wpage = jnp.take_along_axis(tables, (positions // page_size)[:, None], axis=1)[:, 0]
        wpage = jnp.where(active & (wpage >= 0), wpage, NP)
        woff = positions % page_size
        x, pages = self._paged_forward(
            params, x, pages, wpage, woff, tables, positions[:, None], active[:, None]
        )
        logits = self._logits(params, x)[:, 0, :]
        return self._sample_greedy(logits), pages

    def prefill_chunk_paged(
        self, params, pages, table_row, tokens, start_pos, n_valid, slot=None, *, page_size: int
    ):
        """One chunk of one slot's prompt: write positions
        start_pos..start_pos+n_valid-1 into the pool and return the greedy
        next token after the chunk's LAST valid position (meaningful only
        on the final chunk — the request's first generated token).

        tokens [C] int32 (tail chunks are padded; padding masked by
        n_valid); table_row [MP] int32; start_pos / n_valid scalars; slot
        the scalar index of the slot the prompt was admitted to (where the
        model keeps per-slot state beside the pages).  The
        chunk length C is static, so a prompt of any length runs as
        ceil(P/C) calls of ONE compiled program — chunked prefill never
        adds a shape, and in-flight decode streams wait at most one chunk
        (engine/DESIGN.md)."""
        cd = self.config.compute_dtype
        C = tokens.shape[0]
        NP = pages[0].shape[1]

        pos = start_pos + jnp.arange(C)  # [C]
        valid_q = jnp.arange(C) < n_valid
        x = params["tok_emb"].astype(cd)[tokens][None]  # [1, C, E]
        wpage = table_row[pos // page_size]
        wpage = jnp.where(valid_q & (wpage >= 0), wpage, NP)
        woff = pos % page_size
        # causal over the slot's logical context, chunk included (K/V land
        # in the pool before a layer attends)
        x, pages = self._paged_forward(
            params, x, pages, wpage, woff, table_row[None], pos[None], valid_q[None], slot
        )
        logits = self._logits(params, x[0])  # [C, V]
        last = jnp.clip(n_valid - 1, 0, C - 1)
        return self._sample_greedy(logits[last]), pages


# ------------------------------------- a conv window in the pool (per-slot state)


def starts_sequence(q_pos, q_valid):
    """[B]: the call's rows that begin a sequence (valid, at position 0).  A
    model with per-slot state starts such a row from zeros, so a reused slot
    starts clean without the engine's help."""
    return q_valid[:, 0] & (q_pos[:, 0] == 0)


# A causal depthwise convolution whose last k - 1 inputs a slot ride in the
# pool (``models/jamba.py``: in front of the selective scan; ``models/lfm2.py``:
# the whole mixer).  ``member`` is the pool's window member [L, slots, k - 1,
# *channels]; a decode step's rows are the slots (``slot`` None), a prefill
# chunk is one row, of slot ``slot``.  Two functions, because what a model does
# between the taps and the write-back (an activation, a bias) is its own.


def conv_window_taps(member, li: int, slot, u, w, q_pos, q_valid, tiles=lambda a: a):
    """The taps over layer ``li``'s window and the call's own inputs u [B, S,
    channels]: y(t) = sum_j w[j] * in(t - (k-1) + j), w [k, channels], in
    float32.  A row that begins a sequence (``starts_sequence``) reads a zero
    window.  ``tiles`` lays channels out as the member has them (Jamba: the
    scan's tiles of 128).  Returns (y [B, S, *channels] float32, seq [B, k-1 +
    S, *channels]: the window and then the inputs, for ``conv_window_after``,
    and the rows that began a sequence [B], for a caller that keeps more
    state than the window)."""
    K, S = w.shape[0], u.shape[1]
    win = member[li] if slot is None else jax.lax.dynamic_slice_in_dim(member[li], slot, 1, axis=0)  # [B, k-1, *channels]
    fresh = starts_sequence(q_pos, q_valid)
    win = jnp.where(fresh[(slice(None),) + (None,) * (win.ndim - 1)], jnp.zeros_like(win), win)
    seq = jnp.concatenate([win, tiles(u)], axis=1)
    w = tiles(w.astype(jnp.float32))
    return sum(seq[:, j : j + S].astype(jnp.float32) * w[j] for j in range(K)), seq, fresh


def conv_window_after(member, li: int, slot, seq, q_valid):
    """The member with layer ``li``'s window after the call: the k - 1 inputs
    that end at each row's last VALID one (``q_valid`` [B, S]; a chunk's
    padded tail leaves no trace, a row that is not valid leaves the window as
    it was)."""
    k1 = member.shape[2]
    if seq.shape[1] == k1 + 1:
        win = jnp.where(q_valid[(slice(None), slice(None)) + (None,) * (seq.ndim - 2)], seq[:, 1:], seq[:, :-1])
    else:
        win = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(s, n, k1, axis=0))(seq, q_valid.sum(-1))
    if slot is None:
        return member.at[li].set(win)
    return jax.lax.dynamic_update_slice(member, win[None], (li, slot) + (0,) * (member.ndim - 2))
