"""FLOP and byte counts against hand counts for both configurations."""
import json

from bench import flops as F
from bench.spec import BENCH

OLMO = json.loads((BENCH / "configs" / "olmo-1b.json").read_text())
NEMO = json.loads((BENCH / "configs" / "mistral-nemo-12b.json").read_text())


def test_olmo_train_flops_per_token():
    # per layer: q, k, v, o 4 x 2048^2 and SwiGLU 3 x 2048 x 8192
    layer = 4 * 2048 * 2048 + 3 * 2048 * 8192
    assert F.layer_matmul_params(OLMO) == layer == 67_108_864
    n = 4 * layer + 2048 * 50304           # tied head counted once
    assert F.matmul_params(OLMO) == n == 371_458_048
    attn = 12 * 4 * 16 * 128 * 1024
    assert F.train_flops_per_token(OLMO, 1024) == 6 * n + attn
    assert abs(F.train_flops_per_token(OLMO, 1024) / 1e9 - 2.3295) < 1e-3


def test_nemo_decode_step_cost():
    # per layer: q and o 5120 x 4096, k and v 5120 x 1024, SwiGLU
    layer = 2 * 5120 * 4096 + 2 * 5120 * 1024 + 3 * 5120 * 14336
    assert F.layer_matmul_params(NEMO) == layer == 272_629_760
    n = 8 * layer + 5120 * 131072
    live = [100, 300, 1]
    flops, nbytes = F.decode_step_cost(NEMO, live)
    assert flops == 2 * n * 3 + 4 * 8 * 32 * 128 * 401
    row = 2 * 8 * 128 * 2                   # K and V of one position, bf16
    assert nbytes == n * 2 + 8 * row * (401 + 3)
    assert abs(nbytes / 1e9 - 5.71) < 0.01  # weights dominate


def test_decode_bound_is_bandwidth_at_cell_sizes():
    flops, nbytes = F.decode_step_cost(NEMO, [300] * 32)
    assert flops / 197e12 < nbytes / 819e9
