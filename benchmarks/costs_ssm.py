"""Operations and bytes of a dense decoder of Mamba-1 selective-scan layers
and attention layers with a tied head (Jamba keys), from the published keys of
a configuration file.  Beside ``costs.py``, ``costs_moe.py`` and
``costs_hybrid.py`` and for the same reason: the yardstick is kept with the
benchmark.

What an ideal implementation on this device must do, not what the program
does: a call reads every weight once (the tied matrix once, as the head; the
embedding is gathered by row), the K/V of the attending layers' live context
once, and each row's recurrent state and conv window once in and once out."""

from __future__ import annotations

from typing import Mapping

from benchmarks.costs import _round_up


def layer_kinds(cfg: Mapping):
    return ["attn" if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"] else "mamba" for i in range(cfg["num_hidden_layers"])]


def d_inner(cfg: Mapping) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def mamba_mixer_params(cfg: Mapping) -> int:
    """in_proj, the conv and its bias, x_proj, the three inner norms, dt_proj and its bias, A_log, D, out_proj."""
    E, Dn, N, R, K = cfg["hidden_size"], d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    return E * 2 * Dn + Dn * K + Dn + Dn * (R + 2 * N) + (R + 2 * N) + R * Dn + Dn + Dn * N + Dn + Dn * E


def attn_mixer_params(cfg: Mapping) -> int:
    E = cfg["hidden_size"]
    kv = cfg["num_key_value_heads"] * (E // cfg["num_attention_heads"])
    return 2 * E * E + 2 * E * kv


def ffn_params(cfg: Mapping) -> int:
    """The SwiGLU and the layer's two block norms."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"] + 2 * cfg["hidden_size"]


def layer_params(cfg: Mapping) -> int:
    return sum((mamba_mixer_params(cfg) if k == "mamba" else attn_mixer_params(cfg)) + ffn_params(cfg) for k in layer_kinds(cfg))


def num_params(cfg: Mapping) -> int:
    """As published: the embedding is the head too and counts once."""
    return layer_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def weight_bytes(cfg: Mapping, itemsize: int = 2) -> float:
    """Every layer's weights, the final norm, and the tied matrix once (as the head)."""
    return float((layer_params(cfg) + cfg["hidden_size"] * _round_up(cfg["vocab_size"], 128) + cfg["hidden_size"]) * itemsize)


def kv_bytes_per_token(cfg: Mapping, itemsize: int = 2) -> float:
    """K and V of the ATTENDING layers only."""
    head_dim = cfg["hidden_size"] // cfg["num_attention_heads"]
    return float(2 * layer_kinds(cfg).count("attn") * cfg["num_key_value_heads"] * head_dim * itemsize)


def state_bytes_per_slot(cfg: Mapping, window_itemsize: int = 2) -> float:
    """The Mamba layers' float32 state and conv window of one slot."""
    state = d_inner(cfg) * cfg["mamba_d_state"] * 4
    window = (cfg["mamba_d_conv"] - 1) * d_inner(cfg) * window_itemsize
    return float(layer_kinds(cfg).count("mamba") * (state + window))


def decode_step_min_bytes(cfg: Mapping, rows: float, live_context_tokens: float) -> float:
    """Least HBM traffic of one decode step over ``rows`` sequences."""
    return weight_bytes(cfg) + live_context_tokens * kv_bytes_per_token(cfg) + 2.0 * rows * state_bytes_per_slot(cfg)


def scan_flops_per_row(cfg: Mapping) -> float:
    """One row through one layer's recurrence: per state element the decay's
    product, its exponential, the decayed state, the input's product and sum,
    the output's product and sum (7), and per channel dt * u and D * u."""
    return d_inner(cfg) * (7.0 * cfg["mamba_d_state"] + 3.0)


def flops_per_token(cfg: Mapping, context: float) -> float:
    """FLOPs of one token's forward pass through the layers: every matrix
    (2 a parameter; the vectors left out), attention scores and values over
    ``context`` keys in the attending layers, the recurrence and the conv in
    the Mamba ones."""
    kinds = layer_kinds(cfg)
    E, Dn, N, R, K = cfg["hidden_size"], d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    vectors = len(kinds) * 2 * E + kinds.count("mamba") * (Dn * K + Dn + (R + 2 * N) + Dn + Dn * N + Dn)
    attention = kinds.count("attn") * 4.0 * E * context
    mamba = kinds.count("mamba") * (scan_flops_per_row(cfg) + 2.0 * Dn * K)
    return float(2.0 * (layer_params(cfg) - vectors) + attention + mamba)


def prefill_chunk_min_seconds(cfg: Mapping, rows: float, context: float, peaks: Mapping) -> float:
    """Least time of one prefill chunk of ``rows`` valid rows whose last row
    sees ``context`` keys: the larger of its FLOPs over the peak (rows times
    the FLOPs a token at the mean causal context, plus the head for the one row
    that is sampled) and its bytes over the bandwidth (weights once, the
    context's K/V once, one slot's state in and out)."""
    head = cfg["hidden_size"] * _round_up(cfg["vocab_size"], 128)
    flops = rows * flops_per_token(cfg, max(0.0, context - rows / 2.0)) + 2.0 * head
    nbytes = weight_bytes(cfg) + context * kv_bytes_per_token(cfg) + 2.0 * state_bytes_per_slot(cfg)
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def scan_min_seconds(cfg: Mapping, rows: float, sequences: float, peaks: Mapping) -> float:
    """Least time of ONE layer's scan over ``rows`` rows of ``sequences``
    sequences (a chunk: its valid rows of 1; a decode step: its rows, each a
    sequence): the larger of its operations over the peak and its bytes over
    the bandwidth -- u and dt in and y out (float32), B and C, each sequence's
    state once in and once out, A and D once."""
    Dn, N = d_inner(cfg), cfg["mamba_d_state"]
    nbytes = 4.0 * (rows * (3 * Dn + 2 * N) + sequences * 2 * Dn * N + Dn * N + Dn)
    return max(rows * scan_flops_per_row(cfg) / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
