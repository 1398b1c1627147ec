"""moonlight-16b-a3b [moe] — DeepSeek-V3 block: latent attention (MLA),
64 sigmoid-routed experts top-6 with a selection bias, 2 shared experts,
one leading dense layer.
[hf:moonshotai/Moonlight-16B-A3B config.json; arXiv:2405.04434, 2412.19437]

Departures from the published model, each also made by the benchmark's
plain reference:

- RoPE rotates halves of the 64-wide rope part of each q/k head; the
  published code rotates interleaved pairs. A fixed permutation of the
  rope columns of W_q and W_kva maps one form onto the other.
- The selection bias is drawn from the seed with std ``bias_std``
  (assumed: 0.01) and is never updated. In published training a rule
  outside the optimizer moves it after each step; that rule is left out.
- The latent norm's eps is 1e-6 (the published code's default for it);
  the balance loss's coefficient (``aux_coef``, alpha) 0.001 is the
  DeepSeek-V2/V3 config class's default, which the config does not give.
"""
from repro.configs.base import ArchConfig, MoEConfig, register

CONFIG = register(ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    n_layers=27,
    first_k_dense=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,
    vocab_size=163_840,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50_000.0,
    norm_eps=1e-5,
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408,
                  n_shared_experts=2, d_ff_shared=1408,
                  score_func="sigmoid", routed_scaling=2.446, bias_std=0.01,
                  aux_coef=0.001, seq_aux=True),
    source="hf:moonshotai/Moonlight-16B-A3B",
))
