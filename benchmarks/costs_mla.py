"""Operations and bytes of a latent-attention expert decoder (DeepSeek-V3 keys:
``kv_lora_rank``, ``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``,
``first_k_dense_replace`` leading dense layers, then ``n_routed_experts``
experts of ``moe_intermediate_size``, ``num_experts_per_tok`` a token, beside
``n_shared_experts`` shared ones), from the published keys of a configuration
file.  Beside ``costs.py`` and ``costs_moe.py`` and for the same reason: the
yardstick is kept with the benchmark.

What an ideal implementation must do, not what the program does: a token's
expert FLOPs are those of the experts it was routed to, a call reads the
weights of the experts SOME row was routed to, each once, and of the cache
what it KEEPS a position: one row of ``kv_lora_rank + qk_rope_head_dim``
values a layer, once for all heads.  Attention's FLOPs are counted in the
cheaper, unabsorbed form (a (query, cached) pair costs 2 H (dn + dr + dv)),
without the expansion of the cached rows that form needs: a floor under
either form, so a share of it cannot pass 100%."""

from __future__ import annotations

from typing import Mapping

from benchmarks.costs import _round_up


def latent_dim(cfg: Mapping) -> int:
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def attention_params(cfg: Mapping) -> int:
    """W_q, W_dkv, the latent norm, W_ukv, W_o and the block's two norms."""
    E, H, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return E * H * qk + E * latent_dim(cfg) + r + r * H * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]) + H * cfg["v_head_dim"] * E + 2 * E


def dense_ffn_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def outside_experts_params(cfg: Mapping) -> int:
    """An expert layer's router, selection bias and shared experts."""
    E, X = cfg["hidden_size"], cfg["n_routed_experts"]
    return E * X + X + 3 * E * cfg["n_shared_experts"] * cfg["moe_intermediate_size"]


def expert_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _layers(cfg: Mapping):
    """(dense layers, expert layers)."""
    return cfg["first_k_dense_replace"], cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def num_params(cfg: Mapping) -> int:
    """Parameters held: as published, embedding and head at ``vocab_size`` rows."""
    E = cfg["hidden_size"]
    dense, sparse = _layers(cfg)
    return (2 * cfg["vocab_size"] * E + E + cfg["num_hidden_layers"] * attention_params(cfg) + dense * dense_ffn_params(cfg)
            + sparse * (outside_experts_params(cfg) + cfg["n_routed_experts"] * expert_params(cfg)))


def experts_touched(cfg: Mapping, rows: float) -> float:
    """Expected number of a layer's experts that at least one of ``rows`` rows
    is routed to, for routing uniform over experts: X (1 - (1 - K/X)^rows).
    Of 64, top 6: 63.2 at 44 rows, 64.0 at 256."""
    X, K = cfg["n_routed_experts"], cfg["num_experts_per_tok"]
    return X * (1.0 - (1.0 - K / X) ** max(0.0, rows))


def weight_bytes(cfg: Mapping, rows: float, itemsize: int = 2) -> float:
    """Bytes of the weights a call over ``rows`` rows must read: every layer's
    attention matrices, the dense layers' FFN, the expert layers' router and
    shared experts and the routed experts some row is routed to, and the head
    (with the final norm).  The embedding is gathered by row (ignored)."""
    dense, sparse = _layers(cfg)
    head = cfg["hidden_size"] * _round_up(cfg["vocab_size"], 128) + cfg["hidden_size"]
    held = cfg["num_hidden_layers"] * attention_params(cfg) + dense * dense_ffn_params(cfg) + sparse * (outside_experts_params(cfg) + experts_touched(cfg, rows) * expert_params(cfg))
    return float((held + head) * itemsize)


def cache_bytes_per_position(cfg: Mapping, itemsize: int = 2) -> float:
    """What the cache keeps a position, all layers: one latent row each."""
    return float(cfg["num_hidden_layers"] * latent_dim(cfg) * itemsize)


def decode_step_min_bytes(cfg: Mapping, rows: float, live_positions: float) -> float:
    """Least HBM traffic of one decode step over ``rows`` sequences that hold
    ``live_positions`` positions together: the weights above once, and each
    live position's latent rows once, for all heads."""
    return weight_bytes(cfg, rows) + live_positions * cache_bytes_per_position(cfg)


def routed_flops_per_token(cfg: Mapping, context: float) -> float:
    """FLOPs of one token's forward pass through the layers: the attention
    matrices, a dense layer's FFN or an expert layer's router, shared experts
    and the ``num_experts_per_tok`` experts it is routed to, and scores and
    values over ``context`` cached positions in the unabsorbed form."""
    E, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dense, sparse = _layers(cfg)
    vector = 2 * E + cfg["kv_lora_rank"]  # the norms' scales: no matmul
    matmul_params = (cfg["num_hidden_layers"] * (attention_params(cfg) - vector) + dense * dense_ffn_params(cfg)
                     + sparse * (outside_experts_params(cfg) - cfg["n_routed_experts"] + cfg["num_experts_per_tok"] * expert_params(cfg)))
    pair = 2.0 * H * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
    return float(2.0 * matmul_params + cfg["num_hidden_layers"] * pair * context)


def prefill_chunk_min_seconds(cfg: Mapping, rows: float, context: float, peaks: Mapping) -> float:
    """Least time of one prefill chunk of ``rows`` valid rows whose last row
    sees ``context`` positions: the larger of its FLOPs over the peak (rows
    times the routed FLOPs at the mean causal context, plus the head for the
    one row that is sampled) and its bytes over the bandwidth (weights once,
    the context's latent rows once)."""
    head = cfg["hidden_size"] * _round_up(cfg["vocab_size"], 128)
    flops = rows * routed_flops_per_token(cfg, max(0.0, context - rows / 2.0)) + 2.0 * head
    nbytes = weight_bytes(cfg, rows) + context * cache_bytes_per_position(cfg)
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
