"""Operations and bytes of the MoE training cell (DeepSeek-V3 block: MLA
and routed experts), from the configuration file's keys and the program's
routing counters.

As ``flops.py``: a step is forward and backward (three forward products),
recomputation (remat) is not counted in the model's FLOPs, attention is
counted over the full S x S score matrix (PaLM, arXiv:2204.02311,
appendix B). The routed experts are counted over the (token, choice)
pairs routed to the experts held here, ``moe/held_rows``, which the
program counts in each step, summed over its expert layers.
"""
from __future__ import annotations


def _layers(cfg) -> tuple[int, int]:
    """(dense layers, expert layers)."""
    lead = cfg["first_k_dense_replace"]
    return lead, cfg["num_hidden_layers"] - lead


def mla_params(cfg) -> int:
    """Matmul parameters of one latent attention: W_q, W_kva, W_kvb, W_o."""
    d, h, r = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["kv_lora_rank"])
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + dv)
            + h * dv * d)


def expert_params(cfg) -> int:
    """Matmul parameters of one routed expert (gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def token_params(cfg) -> int:
    """Matmul parameters every token meets, the routed experts left out:
    attention, the dense layers' MLP, the router and shared experts of
    each expert layer, and the head."""
    d = cfg["hidden_size"]
    lead, n_moe = _layers(cfg)
    shared = cfg["n_shared_experts"] * expert_params(cfg)
    return ((lead + n_moe) * mla_params(cfg)
            + lead * 3 * d * cfg["intermediate_size"]
            + n_moe * (d * cfg["n_routed_experts"] + shared)
            + d * cfg["vocab_size"])


def train_step_flops(cfg, tokens: int, seq_len: int,
                     held_rows: float) -> float:
    """Model FLOPs of one training step over ``tokens`` tokens in
    sequences of ``seq_len``, ``held_rows`` routed pairs computed here."""
    lead, n_moe = _layers(cfg)
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = 2.0 * cfg["num_attention_heads"] * seq_len * (
        qk + cfg["v_head_dim"]) * (lead + n_moe)
    forward = tokens * (2.0 * token_params(cfg) + attn) \
        + 2.0 * held_rows * expert_params(cfg)
    return 3.0 * forward


def expert_gemm_cost(cfg, gemm_rows: float, weight_bytes: int = 2,
                     row_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one step's grouped expert products over
    ``gemm_rows`` rows (padding included, summed over the expert layers),
    as they run: the forward, its recomputation, and the backward's two
    products (rows and weights), each of gate, up and down. Bytes: each
    product reads its rows and writes its result, and the held experts'
    weights are read by three of the four and written (their gradient)
    by the fourth."""
    d, ff = cfg["hidden_size"], cfg["moe_intermediate_size"]
    _, n_moe = _layers(cfg)
    passes = 4
    flops = passes * 2.0 * gemm_rows * expert_params(cfg)
    rows = passes * 3 * gemm_rows * (d + ff) * row_bytes
    weights = passes * n_moe * cfg["n_experts_held"] * expert_params(cfg) \
        * weight_bytes
    return flops, float(rows + weights)
