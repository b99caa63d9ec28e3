"""Operations and bytes of a hybrid decoder of Gated DeltaNet and gated
attention layers with a held share of routed experts (Qwen3-Next keys), from
the published keys of a configuration file.  Beside ``costs.py`` and
``costs_moe.py`` and for the same reason: the yardstick is kept with the
benchmark.

What an ideal implementation on THIS device must do, not what the program
does: a token's expert FLOPs are those of its routed experts that are held
here (``num_experts`` of the router's ``num_experts_published``), a call reads
the weights of the held experts SOME row was routed to, each once, the K/V of
the full layers' live context once, and each decoding row's recurrent state
once in and once out."""

from __future__ import annotations

from typing import Mapping

from benchmarks.costs import _round_up


def layer_kinds(cfg: Mapping):
    return ["full" if (i + 1) % cfg["full_attention_interval"] == 0 else "linear" for i in range(cfg["num_hidden_layers"])]


def _conv_dim(cfg: Mapping) -> int:
    return 2 * cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"] + cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]


def linear_mixer_params(cfg: Mapping) -> int:
    """W_qkvz, W_ba, the conv kernel, A_log, dt_bias, the output norm, W_out."""
    E, Hv = cfg["hidden_size"], cfg["linear_num_value_heads"]
    v_dim = Hv * cfg["linear_value_head_dim"]
    return E * (_conv_dim(cfg) + v_dim) + E * 2 * Hv + _conv_dim(cfg) * cfg["linear_conv_kernel_dim"] + 2 * Hv + cfg["linear_value_head_dim"] + v_dim * E


def full_mixer_params(cfg: Mapping) -> int:
    """W_q (query and gate), W_k, W_v, W_o and the two per-head norm scales."""
    E, H, KV, D = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    return E * H * 2 * D + 2 * E * KV * D + H * D * E + 2 * D


def outside_experts_params(cfg: Mapping) -> int:
    """A layer's two block norms, router over all published experts, shared expert and its gate."""
    E = cfg["hidden_size"]
    return 2 * E + E * cfg["num_experts_published"] + 3 * E * cfg["shared_expert_intermediate_size"] + E


def expert_params(cfg: Mapping) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def mixer_params(cfg: Mapping) -> int:
    """All layers' mixers and what lies outside the experts."""
    return sum(linear_mixer_params(cfg) if k == "linear" else full_mixer_params(cfg) for k in layer_kinds(cfg)) + cfg["num_hidden_layers"] * outside_experts_params(cfg)


def num_params(cfg: Mapping) -> int:
    """Parameters held: as published, embedding and head at ``vocab_size`` rows."""
    E = cfg["hidden_size"]
    return mixer_params(cfg) + cfg["num_hidden_layers"] * cfg["num_experts"] * expert_params(cfg) + 2 * cfg["vocab_size"] * E + E


def experts_touched(cfg: Mapping, rows: float) -> float:
    """Expected number of a layer's HELD experts that at least one of ``rows``
    rows is routed to, for routing uniform over the published experts:
    X_held (1 - (1 - K / X_published)^rows).  Of 128 held of 512, top 10: 22.9
    at 10 rows, 59.9 at 32, 127.2 at 256."""
    return cfg["num_experts"] * (1.0 - (1.0 - cfg["num_experts_per_tok"] / cfg["num_experts_published"]) ** max(0.0, rows))


def weight_bytes(cfg: Mapping, rows: float, itemsize: int = 2) -> float:
    """Bytes of the weights a call over ``rows`` rows must read: the mixers,
    routers and shared experts, the held experts some row is routed to, and
    the head's slice (with the final norm).  The embedding is gathered by row
    (ignored)."""
    head = cfg["hidden_size"] * _round_up(cfg["vocab_size"], 128) + cfg["hidden_size"]
    touched = cfg["num_hidden_layers"] * experts_touched(cfg, rows) * expert_params(cfg)
    return float((mixer_params(cfg) + touched + head) * itemsize)


def kv_bytes_per_token(cfg: Mapping, itemsize: int = 2) -> float:
    """K and V of the FULL layers only."""
    return float(2 * layer_kinds(cfg).count("full") * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize)


def state_bytes_per_slot(cfg: Mapping, window_itemsize: int = 2) -> float:
    """The linear layers' float32 recurrent state and conv window of one slot."""
    state = cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"] * 4
    window = (cfg["linear_conv_kernel_dim"] - 1) * _conv_dim(cfg) * window_itemsize
    return float(layer_kinds(cfg).count("linear") * (state + window))


def decode_step_min_bytes(cfg: Mapping, rows: float, live_context_tokens: float) -> float:
    """Least HBM traffic of one decode step over ``rows`` sequences: the
    weights above once, the full layers' K/V of the live context once, and
    each row's state read and written once."""
    return weight_bytes(cfg, rows) + live_context_tokens * kv_bytes_per_token(cfg) + 2.0 * rows * state_bytes_per_slot(cfg)


def routed_flops_per_token(cfg: Mapping, context: float) -> float:
    """FLOPs of one token's forward pass through the layers on this device:
    the mixers' matrices, router and shared expert, its routed experts that
    are held here (``num_experts_per_tok`` times the held share), attention
    scores and values over ``context`` keys in the full layers, and the
    recurrence itself in the linear ones (decay, S^T k, the rank-one update
    and S^T q: 7 Dk Dv a value head)."""
    kinds = layer_kinds(cfg)
    E, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    vector = L * 3 * E + kinds.count("linear") * (_conv_dim(cfg) * cfg["linear_conv_kernel_dim"] + 2 * cfg["linear_num_value_heads"] + cfg["linear_value_head_dim"]) + kinds.count("full") * 2 * cfg["head_dim"]
    routed_here = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_experts_published"]
    matmul_params = mixer_params(cfg) - vector + L * routed_here * expert_params(cfg)
    attention = kinds.count("full") * 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * context
    recurrence = kinds.count("linear") * 7.0 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]
    return float(2.0 * matmul_params + attention + recurrence)


def prefill_chunk_min_seconds(cfg: Mapping, rows: float, context: float, peaks: Mapping) -> float:
    """Least time of one prefill chunk of ``rows`` valid rows whose last row
    sees ``context`` keys: the larger of its FLOPs over the peak (rows times
    the routed FLOPs at the mean causal context, plus the head's slice for
    the one row that is sampled) and its bytes over the bandwidth (weights
    once, the context's K/V once, one slot's state in and out)."""
    head = cfg["hidden_size"] * _round_up(cfg["vocab_size"], 128)
    flops = rows * routed_flops_per_token(cfg, max(0.0, context - rows / 2.0)) + 2.0 * head
    nbytes = weight_bytes(cfg, rows) + context * kv_bytes_per_token(cfg) + 2.0 * state_bytes_per_slot(cfg)
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
