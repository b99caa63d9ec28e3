"""Operations and bytes of a short-convolution expert decoder (lfm2_moe keys:
``layer_types`` of "conv" / "full_attention", ``conv_L_cache`` taps,
``num_dense_layers`` leading dense layers of ``intermediate_size``, then
``num_experts`` experts of ``moe_intermediate_size``, ``num_experts_per_tok``
a token, no shared expert, the head tied to the embedding), from the
published keys of a configuration file.  Beside ``costs.py`` and
``costs_moe.py`` and for the same reason: the yardstick is kept with the
benchmark.

What an ideal implementation must do, not what the program does: a token's
expert FLOPs are those of the experts it was routed to (4, not 64), a call
reads the weights of the experts SOME row was routed to, each once, the tied
matrix once, of the cache what it KEEPS a position (K and V of the ATTENDING
layers only), and of the windows what a row's conv layers keep: ``conv_L_cache
- 1`` rows of ``hidden_size`` a layer, once in and once out."""

from __future__ import annotations

from typing import Mapping, Tuple

from benchmarks import costs_moe
from benchmarks.costs import _round_up

experts_touched = costs_moe.experts_touched  # X (1 - (1 - K/X)^rows), from ``num_experts`` and ``num_experts_per_tok``: of 64, top 4: 63.6 at 80 rows


def layer_kinds(cfg: Mapping) -> Tuple[int, int]:
    """(conv layers, attending layers) among the first ``num_hidden_layers`` of ``layer_types``."""
    kinds = cfg["layer_types"][: cfg["num_hidden_layers"]]
    return kinds.count("conv"), kinds.count("full_attention")


def _head_dim(cfg: Mapping) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def conv_mixer_params(cfg: Mapping) -> int:
    """in_proj [E, 3E], out_proj [E, E] and the taps [k, E]."""
    E = cfg["hidden_size"]
    return 4 * E * E + cfg["conv_L_cache"] * E


def attention_params(cfg: Mapping) -> int:
    """q and out [E, E], k and v [E, KV * D], the two head norms."""
    E, D = cfg["hidden_size"], _head_dim(cfg)
    return 2 * E * E + 2 * E * cfg["num_key_value_heads"] * D + 2 * D


def dense_ffn_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: Mapping) -> int:
    """An expert layer's router and its selection bias."""
    return cfg["hidden_size"] * cfg["num_experts"] + cfg["num_experts"]


def _mixers_and_norms(cfg: Mapping) -> int:
    n_conv, n_attn = layer_kinds(cfg)
    return n_conv * conv_mixer_params(cfg) + n_attn * attention_params(cfg) + cfg["num_hidden_layers"] * 2 * cfg["hidden_size"]


def _ffn_layers(cfg: Mapping) -> Tuple[int, int]:
    """(dense layers, expert layers)."""
    return cfg["num_dense_layers"], cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def num_params(cfg: Mapping) -> int:
    """Parameters held: as published, the embedding is the head too and counts once."""
    E = cfg["hidden_size"]
    dense, sparse = _ffn_layers(cfg)
    return (cfg["vocab_size"] * E + E + _mixers_and_norms(cfg) + dense * dense_ffn_params(cfg)
            + sparse * (router_params(cfg) + cfg["num_experts"] * expert_params(cfg)))


def weight_bytes(cfg: Mapping, rows: float, itemsize: int = 2) -> float:
    """Bytes of the weights a call over ``rows`` rows must read: every mixer,
    norm and router, the dense layers' FFN, the experts some row is routed to,
    and the tied matrix ONCE, as the head (the embedding is gathered by row:
    ignored)."""
    dense, sparse = _ffn_layers(cfg)
    head = cfg["hidden_size"] * _round_up(cfg["vocab_size"], 128) + cfg["hidden_size"]
    held = _mixers_and_norms(cfg) + dense * dense_ffn_params(cfg) + sparse * (router_params(cfg) + experts_touched(cfg, rows) * expert_params(cfg))
    return float((held + head) * itemsize)


def cache_bytes_per_position(cfg: Mapping, itemsize: int = 2) -> float:
    """What the cache keeps a position: K and V heads of the attending layers; a conv layer keeps nothing a position."""
    return float(2 * layer_kinds(cfg)[1] * cfg["num_key_value_heads"] * _head_dim(cfg) * itemsize)


def window_bytes_per_slot(cfg: Mapping, itemsize: int = 2) -> float:
    """What the pool keeps a slot: ``conv_L_cache - 1`` rows of ``hidden_size`` a conv layer."""
    return float(layer_kinds(cfg)[0] * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * itemsize)


def decode_step_min_bytes(cfg: Mapping, rows: float, live_positions: float) -> float:
    """Least HBM traffic of one decode step over ``rows`` sequences that hold
    ``live_positions`` positions together: the weights above once, each live
    position's K/V in the attending layers once, and each decoding row's
    windows once in and once out."""
    return weight_bytes(cfg, rows) + live_positions * cache_bytes_per_position(cfg) + 2.0 * rows * window_bytes_per_slot(cfg)


def routed_flops_per_token(cfg: Mapping, context: float) -> float:
    """FLOPs of one token's forward pass through the layers: every mixer's
    matrices, a conv layer's taps and two gates, a dense layer's FFN or an
    expert layer's router and the ``num_experts_per_tok`` experts the token is
    routed to, and scores and values over ``context`` cached positions in the
    attending layers (a (query, cached) pair costs 4 E there)."""
    E = cfg["hidden_size"]
    n_conv, n_attn = layer_kinds(cfg)
    dense, sparse = _ffn_layers(cfg)
    matmul_params = (n_conv * 4 * E * E + n_attn * (attention_params(cfg) - 2 * _head_dim(cfg)) + dense * dense_ffn_params(cfg)
                     + sparse * (E * cfg["num_experts"] + cfg["num_experts_per_tok"] * expert_params(cfg)))
    elementwise = n_conv * (2 * cfg["conv_L_cache"] + 2) * E  # the taps, B * u and C * c
    return float(2.0 * matmul_params + elementwise + n_attn * 4.0 * E * context)


def prefill_chunk_min_seconds(cfg: Mapping, rows: float, context: float, peaks: Mapping) -> float:
    """Least time of one prefill chunk of ``rows`` valid rows whose last row
    sees ``context`` positions: the larger of its FLOPs over the peak (rows
    times the routed FLOPs at the mean causal context, plus the head for the
    one row that is sampled) and its bytes over the bandwidth (weights once,
    the context's K/V once, the slot's windows in and out)."""
    head = cfg["hidden_size"] * _round_up(cfg["vocab_size"], 128)
    flops = rows * routed_flops_per_token(cfg, max(0.0, context - rows / 2.0)) + 2.0 * head
    nbytes = weight_bytes(cfg, rows) + context * cache_bytes_per_position(cfg) + 2.0 * window_bytes_per_slot(cfg)
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
