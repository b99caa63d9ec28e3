"""DeepSeek-V3's block (``model_type: deepseek_v3``; transformers'
``modeling_deepseek_v3.py`` is the published description; Moonlight-16B-A3B
is the family's 16 B member), serving path: multi-head LATENT attention, a
leading dense layer, then layers of routed experts under a sigmoid router
with a selection bias, beside shared experts.

    x += mla(norm(x));  x += ffn(norm(x))        norm: w * rmsnorm(x), eps 1e-5

Latent attention, for a row x at position t (r = ``kv_lora_rank``, dn / dr =
``qk_nope_head_dim`` / ``qk_rope_head_dim``, dv = ``v_head_dim``):

    q = h W_q -> H heads of [q_nope (dn) | q_rope (dr)];   q_rope <- rope(q_rope, t)
    [c (r) | k_rope (dr)] = h W_dkv;   c <- rmsnorm_r(c);   k_rope <- rope(k_rope, t)
    k_nope_h = c W_uk_h,  v_h = c W_uv_h                    per head, ONE k_rope for all
    s_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_rope(s)) / sqrt(dn + dr)

THE CACHE ROW of a position is ``[c | k_rope]``, r + dr values a layer (576
for Moonlight, against H * (dn + dr + dv) = 5120 as K and V heads), and the
pool holds nothing else: ONE "pages" member [L, pages, page, row], row = r +
dr rounded up to whole lane tiles (640: ``DeepseekV3Config.cache_row_dim``
says why), the rest zeros (``init_pages``).  Both paged programs attend in
the ABSORBED form, equal in exact arithmetic, in which the context's per-head
keys and values are never formed:

    q_lat_h = q_nope_h W_uk_h^T  (r wide);   s_h(t, s) = ([q_lat_h | q_rope_h](t) . row(s)) / sqrt(dn + dr)
    o_h = (sum_s p_h(t, s) c(s)) W_uv_h

that is ``LlamaModel._paged_attend`` with H query heads on one key "head" of
width r + dr whose values are its first r columns: one gather of a block of
rows serves every head's scores and values.

``DeepseekV3Model`` is a ``LlamaModel``: the engine's two paged programs, the
page write, the blockwise walk, the head and the greedy sampler are that
class's, and the routed layer is ``parallel/moe.py dropless_moe_ffn`` told to
score by sigmoid, choose by score + bias, renormalise and scale.  What this
file adds is the latent mixer, ``_paged_forward`` over a dense layer and
expert layers, the shared expert, and the pool.

Layout against the published tensors (a checkpoint loader re-lays once;
``benchmarks/configs`` states it under ``assumed``): ``wq`` columns are per
head [nope | rope] and ``w_dkv`` columns [c | k_rope], as published
(``q_proj``, ``kv_a_proj_with_mqa``); ``kv_b_proj`` [r, H * (dn + dv)] is held
split by head as ``w_uk`` [H, dn, r] and ``w_uv`` [H, r, dv], the two matrices
the absorbed form contracts with; rotary is the "rotate_half" pairing (i, i +
dr/2) the published code applies after permuting the checkpoint's interleaved
pairs; ``n_shared_experts`` shared experts are one SwiGLU of their summed
width, as published.  ``q_lora_rank`` (a low-rank query), ``n_group`` > 1
(group-limited choice) and rope scaling are not here: Moonlight has none.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.models.llama import LlamaConfig, LlamaModel, _rms_norm, _round_up
from ray_tpu.models.qwen3_next import _partial_rope


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config(LlamaConfig):
    """Defaults are the published widths of Moonlight-16B-A3B.
    ``hidden_dim`` is one routed expert's width, ``dense_hidden_dim`` the
    width of the leading ``first_k_dense`` layers' FFN; ``n_kv_heads`` is 1:
    what the cache holds a position is one row that every query head reads."""

    vocab_size: int = 163840
    dim: int = 2048
    n_layers: int = 27
    n_heads: int = 16
    n_kv_heads: int = 1
    hidden_dim: int = 1408
    max_seq_len: int = 8192
    rope_theta: float = 50000.0
    norm_eps: float = 1e-5
    n_experts: int = 64
    n_experts_per_tok: int = 6
    dense_hidden_dim: int = 11264
    first_k_dense: int = 1
    n_shared_experts: int = 2
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    routed_scaling_factor: float = 2.446
    norm_topk_prob: bool = True

    def __post_init__(self):
        super().__post_init__()
        if self.n_kv_heads != 1 or not 0 <= self.first_k_dense <= self.n_layers or self.qk_rope_head_dim % 2:
            raise ValueError("the latent cache is one row a position (n_kv_heads 1), the dense layers lead, rotary dimensions pair up")

    def build_model(self) -> "DeepseekV3Model":
        return DeepseekV3Model(self)

    @property
    def latent_dim(self) -> int:
        """Values the cache keeps a position a layer: [c | k_rope]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row_dim(self) -> int:
        """Width of a position's row AS THE POOL STORES IT: ``latent_dim``
        values, then zeros up to whole lane tiles of 128 (576 -> 640).  At
        576 wide the TPU lays a [.., 16, 576] bf16 member out with the PAGE
        axis minor-most (576 is four and a half lane tiles; that way nothing
        is padded), a page is scattered over the whole member and both
        programs copy the pool into row-major order and back on every call;
        row-major, the device pads each row to 640 itself.  Declaring that
        layout (``jax.experimental.layout``) does not survive the persistent
        compile cache (PERF.md section 7, PRs 34 and 45), so the padding is
        the member's shape: 11% of the pool, and of what the walk reads."""
        return _round_up(self.latent_dim, 128)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def _layer_params(self) -> Dict[str, int]:
        """Parameters of one layer's parts, from the published keys."""
        E, H, r = self.dim, self.n_heads, self.kv_lora_rank
        return {
            # W_q, W_dkv, the latent norm, W_ukv, W_o, and the block's two norms
            "attn": E * H * self.qk_head_dim + E * self.latent_dim + r + r * H * (self.qk_nope_head_dim + self.v_head_dim)
            + H * self.v_head_dim * E + 2 * E,
            "dense": 3 * E * self.dense_hidden_dim,
            # router, its selection bias, the shared experts
            "outside_experts": E * self.n_experts + self.n_experts + 3 * E * self.n_shared_experts * self.hidden_dim,
            "expert": 3 * E * self.hidden_dim,
        }

    def num_params(self) -> int:
        """As published: embedding and head at ``vocab_size`` rows (the
        tree pads both to ``padded_vocab``)."""
        n = self._layer_params()
        sparse = self.n_layers - self.first_k_dense
        return int(2 * self.vocab_size * self.dim + self.dim + self.n_layers * n["attn"] + self.first_k_dense * n["dense"]
                   + sparse * (n["outside_experts"] + self.n_experts * n["expert"]))

    def active_params_per_token(self) -> int:
        idle = (self.n_layers - self.first_k_dense) * (self.n_experts - self.n_experts_per_tok)
        return int(self.num_params() - idle * self._layer_params()["expert"])


class DeepseekV3Model(LlamaModel):
    config: DeepseekV3Config

    # -------------------------------------------------------------- params

    def init(self, rng: jax.Array) -> Dict[str, Any]:
        """Three stacks, because a dense layer and an expert layer do not
        stack into one: ``attn`` [L, ...] (every layer's mixer), ``dense``
        [first_k_dense, ...] and ``moe`` [L - first_k_dense, ...], each FFN
        stack with its block norm.  The selection bias is drawn, not zero
        (the published initial value): N(0, 0.2), the spread of the sigmoid
        scores themselves at these weights, so that it decides choices."""
        cfg = self.config
        E, V, pd, L = cfg.dim, cfg.padded_vocab, cfg.param_dtype, cfg.n_layers
        Ld, Lm = cfg.first_k_dense, cfg.n_layers - cfg.first_k_dense
        H, r, dn, dv, X = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.n_experts
        Hs = cfg.n_shared_experts * cfg.hidden_dim
        k = iter(jax.random.split(rng, 20))
        std, out_std = 0.02, 0.02 / math.sqrt(2 * L)

        def norm(shape, s=std):
            return (jax.random.normal(next(k), shape) * s).astype(pd)

        attn = {
            "attn_norm": jnp.ones((L, E), pd),
            "wq": norm((L, E, H * cfg.qk_head_dim)),
            "w_dkv": norm((L, E, cfg.latent_dim)),
            "kv_norm": jnp.ones((L, r), pd),
            "w_uk": norm((L, H, dn, r)),
            "w_uv": norm((L, H, r, dv)),
            "wo": norm((L, H * dv, E), out_std),
        }
        dense = {
            "ffn_norm": jnp.ones((Ld, E), pd),
            "w_gate": norm((Ld, E, cfg.dense_hidden_dim)),
            "w_up": norm((Ld, E, cfg.dense_hidden_dim)),
            "w_down": norm((Ld, cfg.dense_hidden_dim, E), out_std),
        }
        moe = {
            "ffn_norm": jnp.ones((Lm, E), pd),
            "router": norm((Lm, E, X)),
            "router_bias": norm((Lm, X), 0.2),
            "w_gate": norm((Lm, X, E, cfg.hidden_dim)),
            "w_up": norm((Lm, X, E, cfg.hidden_dim)),
            "w_down": norm((Lm, X, cfg.hidden_dim, E), out_std),
            "ws_gate": norm((Lm, E, Hs)),
            "ws_up": norm((Lm, E, Hs)),
            "ws_down": norm((Lm, Hs, E), out_std),
        }
        return {
            "tok_emb": norm((V, E)), "out_head": norm((E, V)), "final_norm": jnp.ones((E,), pd),
            "attn": attn, "dense": dense, "moe": moe,
        }

    def param_pspecs(self, mesh=None) -> Dict[str, Any]:
        """Experts and vocabulary over tp; the mixers, the dense layer, the
        router and the shared expert whole on every device."""
        shapes = jax.eval_shape(self.init, jax.random.PRNGKey(0))
        specs = jax.tree.map(lambda a: P(*([None] * a.ndim)), shapes)
        for name in ("w_gate", "w_up", "w_down"):
            specs["moe"][name] = P(None, "tp", None, None)
        specs["tok_emb"], specs["out_head"] = P("tp", None), P(None, "tp")
        return specs

    # --------------------------------------------------------------- pool

    def init_pages(self, num_pages: int, page_size: int, num_slots: int = 0) -> Tuple:
        """The pool (``pool_roles`` names its members): ONE pages member, the
        latent rows [L, NP, PS, cache_row_dim] in the compute type (r + dr
        values and the padding ``cache_row_dim`` explains), and the routing
        counter [n_experts] int32.  No member holds an expanded key or
        value."""
        cfg = self.config
        return (
            jnp.zeros((cfg.n_layers, num_pages, page_size, cfg.cache_row_dim), cfg.compute_dtype),
            jnp.zeros((cfg.n_experts,), jnp.int32),
        )

    def pool_pspecs(self) -> Tuple:
        return (P(), P())  # one row for every head: nothing to split

    def pool_roles(self) -> Tuple[str, ...]:
        return ("pages", "counter")

    # ------------------------------------------------------------- forward

    def apply(self, params, tokens, mesh=None):
        raise NotImplementedError("DeepseekV3Model has the serving path only (the paged programs)")

    def _latent(self, x, ap, positions):
        """Normed input -> (absorbed queries [B, S, H, row]: per head
        [q_nope W_uk^T | rope(q_rope) | 0], and each position's cache row [B,
        S, row]: [rmsnorm(c) | rope(k_rope) | 0]; row = ``cache_row_dim``,
        the zeros its padding: a score is over r + dr values)."""
        cfg = self.config
        cd = cfg.compute_dtype
        B, S, _ = x.shape
        H, r, dn = cfg.n_heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim
        h = _rms_norm(x, ap["attn_norm"].astype(jnp.float32), cfg.norm_eps).astype(cd)
        # behind the barrier the two stay plain 2-D matmuls that read their weights where they lie (LlamaModel._qkv)
        q, ckr = jax.lax.optimization_barrier((h @ ap["wq"].astype(cd), h @ ap["w_dkv"].astype(cd)))
        q = q.reshape(B, S, H, cfg.qk_head_dim)
        c = _rms_norm(ckr[..., :r], ap["kv_norm"].astype(jnp.float32), cfg.norm_eps).astype(cd)
        k_rope = _partial_rope(ckr[..., None, r:], positions, cfg.rope_theta, cfg.qk_rope_head_dim)[..., 0, :]
        q_rope = _partial_rope(q[..., dn:], positions, cfg.rope_theta, cfg.qk_rope_head_dim)
        q_lat = jnp.einsum("bshd,hdc->bshc", q[..., :dn], ap["w_uk"].astype(cd))
        pad = cfg.cache_row_dim - cfg.latent_dim
        return (jnp.concatenate([q_lat, q_rope, jnp.zeros((B, S, H, pad), cd)], axis=-1),
                jnp.concatenate([c, k_rope, jnp.zeros((B, S, pad), cd)], axis=-1))

    def _mla(self, x, ap, li: int, pool, wpage, woff, tables, q_pos, q_valid, n_blocks):
        """Latent-attention mixer of layer ``li``: write this call's rows
        into the pool, then the shared walk over it in the absorbed form.
        Returns (what the mixer adds to x, pool)."""
        cfg = self.config
        cd = cfg.compute_dtype
        B, S, _ = x.shape
        H, r = cfg.n_heads, cfg.kv_lora_rank
        with jax.named_scope("mla_project"):
            q, row = self._latent(x, ap, q_pos)
        pool = self._paged_write(pool, li, wpage, woff, row.reshape(-1, cfg.cache_row_dim))
        with jax.named_scope("mla_attend"):
            o_lat = self._paged_attend(q, pool, None, li, tables, q_pos, q_valid, n_blocks, value_dim=r, scale=cfg.qk_head_dim**-0.5)
        with jax.named_scope("mla_project"):
            o = jnp.einsum("bshc,hcd->bshd", o_lat.reshape(B, S, H, r), ap["w_uv"].astype(cd))
            return o.reshape(B, S, H * cfg.v_head_dim) @ ap["wo"].astype(cd), pool

    def _dense_ffn(self, x, fp):
        """A leading layer's FFN: one SwiGLU of ``dense_hidden_dim``."""
        cfg = self.config
        cd = cfg.compute_dtype
        h = _rms_norm(x, fp["ffn_norm"].astype(jnp.float32), cfg.norm_eps).astype(cd)
        return x + (jax.nn.silu(h @ fp["w_gate"].astype(cd)) * (h @ fp["w_up"].astype(cd))) @ fp["w_down"].astype(cd)

    def _ffn(self, x, mp):
        """An expert layer's FFN: x [B, S, E] -> (x + the routed experts'
        weighted sum + the shared expert, once and unweighted; chosen [B, S,
        K])."""
        from ray_tpu.parallel.moe import dropless_moe_ffn

        cfg = self.config
        cd = cfg.compute_dtype
        B, S, E = x.shape
        h = _rms_norm(x, mp["ffn_norm"].astype(jnp.float32), cfg.norm_eps).astype(cd).reshape(B * S, E)
        with jax.named_scope("moe_ffn"):
            y, chosen = dropless_moe_ffn(
                h, mp["router"], mp["w_gate"], mp["w_up"], mp["w_down"], top_k=cfg.n_experts_per_tok,
                renormalize=cfg.norm_topk_prob, scoring="sigmoid", bias=mp["router_bias"], scale=cfg.routed_scaling_factor,
            )
        with jax.named_scope("shared_expert"):
            y = y + (jax.nn.silu(h @ mp["ws_gate"].astype(cd)) * (h @ mp["ws_up"].astype(cd))) @ mp["ws_down"].astype(cd)
        return x + y.reshape(B, S, E), chosen.reshape(B, S, -1)

    def _paged_forward(self, params, x, pages, wpage, woff, tables, q_pos, q_valid, slot=None):
        cfg = self.config
        pool, load = pages
        tables, n_blocks = self._walk_blocks(tables, pool.shape[2], q_pos, q_valid)
        for i in range(cfg.n_layers):
            ap = jax.tree.map(lambda p: p[i], params["attn"])
            attn, pool = self._mla(x, ap, i, pool, wpage, woff, tables, q_pos, q_valid, n_blocks)
            x = x + attn
            if i < cfg.first_k_dense:
                x = self._dense_ffn(x, jax.tree.map(lambda p: p[i], params["dense"]))
                continue
            x, chosen = self._ffn(x, jax.tree.map(lambda p: p[i - cfg.first_k_dense], params["moe"]))
            hits = jax.nn.one_hot(chosen, cfg.n_experts, dtype=jnp.int32) * q_valid[..., None, None]
            load = load + hits.sum((0, 1, 2))
        x = _rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps)
        return x.astype(cfg.compute_dtype), (pool, load)
