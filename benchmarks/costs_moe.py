"""Operations and bytes of a sparse-expert decoder (OLMoE keys: ``num_experts``
experts of width ``intermediate_size``, ``num_experts_per_tok`` a token), from
the published keys of a configuration file.  Beside ``costs.py`` and for the
same reason: the yardstick is kept with the benchmark.

What an ideal implementation must do, not what the program does: a token's
expert FLOPs are those of the experts it was routed to (top-8, not 64), and a
call reads the weights of the experts SOME row was routed to (all 64 once
there are a few dozen rows), each once."""

from __future__ import annotations

from typing import Mapping

from benchmarks.costs import _round_up


def _attention_params(cfg: Mapping) -> int:
    """wq, wk, wv, wo, the two block norms and the two QK-norm scales."""
    E = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2 * E * E + 2 * E * kv + 2 * E + E + kv


def _expert_params(cfg: Mapping) -> int:
    """One expert: gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _head_params(cfg: Mapping) -> int:
    return cfg["hidden_size"] * _round_up(cfg["vocab_size"], 128) + cfg["hidden_size"]


def experts_touched(cfg: Mapping, rows: float) -> float:
    """Expected number of a layer's experts that at least one of ``rows`` rows
    is routed to, for routing uniform over experts: X (1 - (1 - K/X)^rows).
    64 of 64 within 1.4% from 32 rows on; 47 at 10 rows."""
    X, K = cfg["num_experts"], cfg["num_experts_per_tok"]
    return X * (1.0 - (1.0 - K / X) ** max(0.0, rows))


def weight_bytes(cfg: Mapping, rows: float, itemsize: int = 2) -> float:
    """Bytes of the weights a call over ``rows`` rows must read: every layer's
    attention matrices and router, the experts some row is routed to, and the
    output head.  The embedding table is gathered by row (ignored)."""
    E, L, X = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["num_experts"]
    per_layer = _attention_params(cfg) + E * X + experts_touched(cfg, rows) * _expert_params(cfg)
    return float((L * per_layer + _head_params(cfg)) * itemsize)


def kv_bytes_per_token(cfg: Mapping, itemsize: int = 2) -> float:
    return float(2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize)


def decode_step_min_bytes(cfg: Mapping, rows: float, live_context_tokens: float) -> float:
    """Least HBM traffic of one decode step over ``rows`` sequences: the
    weights above once, and the K/V of the live context once."""
    return weight_bytes(cfg, rows) + live_context_tokens * kv_bytes_per_token(cfg)


def routed_flops_per_token(cfg: Mapping, context: float) -> float:
    """FLOPs of one token's forward pass through the layers: attention
    projections, scores and values over ``context`` keys, the router, and the
    ``num_experts_per_tok`` experts it is routed to (not all of them)."""
    E, L, X, K = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["num_experts"], cfg["num_experts_per_tok"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    matmul_params = 2 * E * E + 2 * E * kv + E * X + K * _expert_params(cfg)
    return float(L * (2.0 * matmul_params + 4.0 * E * context))


def prefill_chunk_min_seconds(cfg: Mapping, rows: float, context: float, peaks: Mapping) -> float:
    """Least time of one prefill chunk of ``rows`` valid rows whose last row
    sees ``context`` keys: the larger of its FLOPs over the peak (rows times
    the routed FLOPs at the mean causal context, plus the head for the one row
    that is sampled) and its bytes over the bandwidth (weights once, the
    context's K/V once).  At 256 rows and 8 layers the bytes are five times
    the FLOPs: a prefill chunk of an expert model is bound by weight bytes."""
    flops = rows * routed_flops_per_token(cfg, max(0.0, context - rows / 2.0)) + 2.0 * _head_params(cfg)
    nbytes = weight_bytes(cfg, rows) + context * kv_bytes_per_token(cfg)
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
