"""Operations and bytes that the algorithms need, computed from shapes.  Kept
with the benchmark so that no later PR can move the yardstick.  Inputs are the
published keys of a configuration file, never the program's config objects."""

from __future__ import annotations

from typing import Mapping


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def gpt2_num_params(cfg: Mapping) -> int:
    """Copied from ``GPT2Config.num_params`` (ray_tpu/models/gpt2.py:125):
    tied embedding at the vocabulary padded to 128, learned positions,
    12·E² + 13·E per layer, final layer norm."""
    V = _round_up(cfg["vocab_size"], 128)
    L, E, S = cfg["n_layer"], cfg["n_embd"], cfg["n_positions"]
    return V * E + S * E + L * (12 * E * E + 13 * E) + 2 * E


def gpt2_train_flops_per_token(cfg: Mapping, seq: int) -> float:
    """Copied from ``GPT2Config.flops_per_token`` (models/gpt2.py:130):
    6·N for the parameter matmuls forward and backward, plus 12·L·E·S for the
    attention score and value matmuls.  Recomputation (remat) is not counted,
    and neither is the causal mask's saving: this is the MFU numerator."""
    return 6.0 * gpt2_num_params(cfg) + 12.0 * cfg["n_layer"] * cfg["n_embd"] * seq


def causal_attention_train_flops(batch: int, heads: int, seq: int, head_dim: int, layers: int) -> float:
    """FLOPs a causal attention kernel must do forward and backward for one
    step: QK^T and PV forward (2 matmuls), and dV, dP, dQ, dK backward plus
    the recomputed QK^T (5 matmuls), each 2·S·S·D per head, halved because a
    causal kernel skips the masked half."""
    per_matmul = 2.0 * seq * seq * head_dim
    return batch * heads * layers * 7.0 * per_matmul * 0.5


def causal_attention_train_bytes(batch: int, heads: int, seq: int, head_dim: int, layers: int, itemsize: int = 2) -> float:
    """Least HBM traffic of the same kernels: forward reads Q, K, V and
    writes O; backward reads Q, K, V, O, dO and writes dQ, dK, dV."""
    tensor = batch * heads * seq * head_dim * itemsize
    return layers * 12.0 * tensor


def llama_weight_bytes(cfg: Mapping, itemsize: int = 2) -> float:
    """Bytes of the weights one decode step must read: every layer's
    attention and MLP matrices and the output head; the embedding table is
    gathered by row, so only ``rows`` of it are read (ignored: 16 rows)."""
    E, L, H = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["intermediate_size"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_layer = 2 * E * E + 2 * E * kv + 3 * E * H + 2 * E
    head = E * _round_up(cfg["vocab_size"], 128)
    return float((L * per_layer + head + E) * itemsize)


def llama_kv_bytes_per_token(cfg: Mapping, itemsize: int = 2) -> float:
    return float(2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * cfg["head_dim"] * itemsize)


def decode_step_min_bytes(cfg: Mapping, live_context_tokens: float) -> float:
    """Least HBM traffic of one decode step: the weights once, and the K/V of
    the live context once."""
    return llama_weight_bytes(cfg) + live_context_tokens * llama_kv_bytes_per_token(cfg)
