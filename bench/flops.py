"""Operations and bytes the algorithm needs, from the configuration's sizes.

Counts what the mathematics requires: recomputation (remat) is not
counted, attention is counted over the full (S x S) score matrix as in
PaLM's model-FLOPs formula (arXiv:2204.02311, appendix B), and a decode
step reads each weight once.
"""
from __future__ import annotations


def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def layer_matmul_params(cfg) -> int:
    d, hd = cfg["d_model"], head_dim(cfg)
    attn = d * cfg["n_heads"] * hd * 2 + d * cfg["n_kv_heads"] * hd * 2
    return attn + 3 * d * cfg["d_ff"]


def matmul_params(cfg) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's projections and the head (the embedding lookup is a gather)."""
    return cfg["n_layers"] * layer_matmul_params(cfg) \
        + cfg["d_model"] * cfg["vocab_size"]


def train_flops_per_token(cfg, seq_len: int) -> float:
    """Forward and backward: 6 N plus 12 L H D S for attention."""
    attn = 12 * cfg["n_layers"] * cfg["n_heads"] * head_dim(cfg) * seq_len
    return 6.0 * matmul_params(cfg) + attn


def decode_step_cost(cfg, live: list[int], weight_bytes: int = 2,
                     cache_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) one decode step needs for the busy slots, where
    ``live[i]`` is the number of cache positions slot i attends over
    (its new token included). Bytes: every layer's weights and the head
    read once, each slot's K and V up to its live length read, and the
    new K and V rows written."""
    hd, kv, h = head_dim(cfg), cfg["n_kv_heads"], cfg["n_heads"]
    layers = cfg["n_layers"]
    tokens = len(live)
    ctx = sum(live)
    flops = 2.0 * matmul_params(cfg) * tokens \
        + 4.0 * layers * h * hd * ctx
    row = 2 * kv * hd * cache_bytes            # K and V of one position
    weights = (layers * layer_matmul_params(cfg)
               + cfg["d_model"] * cfg["vocab_size"]) * weight_bytes
    kv_bytes = layers * row * (ctx + tokens)
    return flops, float(weights + kv_bytes)
