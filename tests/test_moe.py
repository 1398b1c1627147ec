"""The dropless MoE layer and latent attention in the program, at tiny
sizes on the CPU: a share of the experts computes its part of the uncut
layer, the grouped product's padding stays within a tile per expert, the
correction bias moves selection and not weights, the decode path refuses
MLA by name, and the expert-parallel path on a 2x2 mesh gives the
one-device result."""
import dataclasses
import functools
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.models import blocks as B
from repro.models import model as M

ROOT = Path(__file__).resolve().parents[1]


def _cfg(n_experts=16, top_k=4, **moe):
    """moonlight-shaped at d 64: MLA, sigmoid routing with a correction
    bias, 2 shared experts."""
    base = get_arch("moonlight-16b-a3b").reduced()
    return dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, n_experts=n_experts, top_k=top_k, d_ff_expert=32,
        d_ff_shared=32, **moe))


def _x(cfg, b=2, s=24, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, cfg.d_model),
                             jnp.float32)


def test_shares_of_the_experts_sum_to_the_uncut_layer():
    """Four shares of four experts each: their routed parts, with the
    shared experts counted once, add up to the layer over all sixteen;
    each share draws its experts as the uncut layer does."""
    cfg = _cfg()
    key = jax.random.PRNGKey(0)
    p = B.init_moe(cfg, key)
    x = _x(cfg)
    with jax.default_matmul_precision("highest"):
        whole, aux, _ = B.moe_block(p, x, cfg)
        shared = B.mlp_block(p["shared"], x)
        parts = []
        for j in range(4):
            cj = dataclasses.replace(cfg, moe=dataclasses.replace(
                cfg.moe, n_held=4, first_held=4 * j))
            pj = B.init_moe(cj, key)
            for w in ("w_gate", "w_up", "w_down"):
                np.testing.assert_array_equal(pj[w], p[w][4 * j:4 * j + 4])
            yj, auxj, stats = B.moe_block(pj, x, cj)
            assert float(auxj) == pytest.approx(float(aux), rel=1e-6)
            parts.append(yj - shared)
    np.testing.assert_allclose(sum(parts) + shared, whole, atol=1e-5,
                               rtol=1e-5)


@pytest.mark.parametrize("held,first", [(16, 0), (4, 8)])
def test_grouped_product_pads_at_most_a_tile_per_expert(held, first):
    cfg = _cfg(n_held=held, first_held=first)
    p = B.init_moe(cfg, jax.random.PRNGKey(1))
    x = _x(cfg, b=4, s=64)
    _, _, stats = B.moe_block(p, x, cfg)
    rows, gemm = int(stats["moe/held_rows"]), int(stats["moe/gemm_rows"])
    # what the router sent to the experts held here
    _, idx, _ = B.route(x.reshape(-1, cfg.d_model), p, cfg.moe)
    here = (np.asarray(idx) >= first) & (np.asarray(idx) < first + held)
    assert rows == int(here.sum()) > 0
    assert rows <= gemm <= rows + held * B.GMM_TILE_ROWS
    counts = np.bincount(np.asarray(idx)[here] - first, minlength=held)
    assert int(stats["moe/max_expert_rows"]) == counts.max()


def test_correction_bias_moves_selection_not_weights():
    cfg = _cfg()
    p = B.init_moe(cfg, jax.random.PRNGKey(2))
    xt = _x(cfg, b=4, s=64).reshape(-1, cfg.d_model)
    scores, idx, w = B.route(xt, p, cfg.moe)
    unbiased = {k: v for k, v in p.items() if k != "router_bias"}
    _, idx0, _ = B.route(xt, unbiased, cfg.moe)
    assert (np.sort(idx, -1) != np.sort(idx0, -1)).any(axis=-1).mean() > 0.1
    # the weights are the picked experts' scores, renormalised and scaled
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    want = picked / picked.sum(-1, keepdims=True) * cfg.moe.routed_scaling
    np.testing.assert_allclose(w, want, rtol=1e-6)
    # and the bias is picked from, never trained
    from repro.train.optimizer import trainable
    flags = jax.tree_util.tree_leaves_with_path(trainable(p))
    assert [str(k[-1].key) for k, f in flags if not f] == ["router_bias"]


def test_optimizer_leaves_the_correction_bias_alone():
    from repro.train.optimizer import (OptimizerConfig, adamw_update,
                                       init_opt_state)
    cfg = _cfg()
    params = jax.jit(lambda k: M.init_params(cfg, k))(jax.random.PRNGKey(0))
    grads = jax.tree.map(jnp.ones_like, params)
    new, _, _ = jax.jit(functools.partial(
        adamw_update, OptimizerConfig(lr=0.1, warmup_steps=0)))(
            params, grads, init_opt_state(params))
    bias = "layers", "moe", "router_bias"
    old_b = params[bias[0]][bias[1]][bias[2]]
    np.testing.assert_array_equal(new["layers"]["moe"]["router_bias"], old_b)
    assert not np.array_equal(new["layers"]["moe"]["router"],
                              params["layers"]["moe"]["router"])


def test_serve_refuses_latent_attention_by_name():
    from repro.launch.serve import serve
    cfg = get_arch("moonlight-16b-a3b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="latent K/V cache"):
        serve(cfg, params, [[1, 2, 3]], slots=2, buffer_len=16, max_new=2)


def test_latent_attention_takes_its_own_v_head_size():
    """The causal core with q/k heads of 24 and v heads of 16: the chunked
    (flash-style) path gives the full path's result and gradients."""
    q, k = (jax.random.normal(jax.random.PRNGKey(i), (2, 64, 4, 24))
            for i in (0, 1))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 4, 16))

    chunked = functools.partial(B.chunked_causal_attention, chunk=16)
    out = chunked(q, k, v)
    assert out.shape == (2, 64, 4, 16)
    np.testing.assert_allclose(out, B.full_causal_attention(q, k, v),
                               atol=1e-5, rtol=1e-5)

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(f(*a) ** 2), (0, 1, 2))(q, k, v)
    for a, b in zip(grads(chunked), grads(B.full_causal_attention)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


TWO_BY_TWO = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, functools
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_arch
from repro.launch.train import build_sharded_train
from repro.models import model as M
from repro.sharding import rules as SR
from repro.sharding.mesh import make_mesh
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import TrainConfig, make_opt_state, make_train_step

base = get_arch("moonlight-16b-a3b").reduced()
cfg = dataclasses.replace(base, moe=dataclasses.replace(
    base.moe, n_experts=8, top_k=4, n_held=4, first_held=2))
tcfg = TrainConfig(compute_dtype="float32", remat="full")
# eps 1: the update stays in proportion to small gradients, so the
# parameters compare the gradients (at eps 1e-8 AdamW's first step is
# lr times the sign of each gradient, round-off near zero included)
ocfg = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=4, eps=1.0)
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
batch = {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}

from repro.models.blocks import GMM_TILE_ROWS
n_moe = cfg.n_layers - cfg.first_k_dense
with jax.default_matmul_precision("highest"):
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    one, _, m1 = jax.jit(make_train_step(cfg, tcfg, ocfg))(
        params, make_opt_state(params, tcfg), batch)
    # 2x2: experts over "model"; 4x1: every device holds all four
    for shape in ((2, 2), (4, 1)):
        mesh = make_mesh(shape, ("data", "model"))
        step, pshard, oshard = build_sharded_train(cfg, tcfg, ocfg, mesh)
        fresh = jax.tree.map(jnp.copy, params)       # the step donates it
        two, _, m2 = step(jax.device_put(fresh, pshard),
                          jax.device_put(make_opt_state(params, tcfg), oshard),
                          batch)
        SR.set_rules(None)
        for k in ("loss", "aux_loss", "grad_norm", "moe/held_rows",
                  "moe/max_expert_rows"):
            print(shape, k, float(m1[k]), float(m2[k]))
            assert abs(float(m1[k]) - float(m2[k])) <= \
                1e-5 * abs(float(m1[k])), k
        # each device pads its own experts' rows to the tile
        held, gemm = int(m2["moe/held_rows"]), int(m2["moe/gemm_rows"])
        assert held <= gemm <= held + n_moe * 4 * 4 * GMM_TILE_ROWS, gemm
        for p0, a, b in zip(*(jax.tree.leaves(t) for t in (params, one, two))):
            d1, d2 = a - p0, b - p0
            gap = float(jnp.linalg.norm(d1 - d2))
            assert gap <= 1e-3 * float(jnp.linalg.norm(d1)) + 1e-9, gap
print("ok")
"""


def test_expert_parallel_step_on_2x2_matches_one_device():
    """The held experts (ids 2-5 of 8) sharded over "model", two on each
    device, tokens over "data"; and on a 4x1 mesh, tokens over four
    devices that each hold all four experts: one train step gives one
    device's loss, balance loss, parameters and counters (rows summed
    over the devices, the largest expert load over all data shards)."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", TWO_BY_TWO], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    assert proc.stdout.strip().endswith("ok")
