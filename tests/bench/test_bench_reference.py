"""The plain reference against the program at tiny sizes, in float32: the
same weights from the same seed, the same logits, the same three AdamW
steps. The reference imports nothing of the program; only this test
holds the two side by side."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import reference as R
from bench.traffic_gen import MarkovStream
from conftest import TINY

SEED = 2**31 + 99


@pytest.mark.parametrize("name", ["tiny", "tinyg"])
def test_weights_and_logits_match_the_program(name):
    from repro.configs.base import ArchConfig
    from repro.models import model as M

    cfg = TINY["configs"][name]
    arch = ArchConfig(**cfg)
    # jitted, as launch/train.py's init_train draws them
    prog = jax.jit(functools.partial(M.init_params, arch))(
        jax.random.PRNGKey(SEED))
    ref = R.init_params(cfg, SEED)
    pa, ra = (jax.tree_util.tree_flatten_with_path(t)[0] for t in (prog, ref))
    assert [p for p, _ in pa] == [p for p, _ in ra]
    for (_, a), (_, b) in zip(pa, ra):
        assert np.array_equal(a, b)
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 24))
    ctx = M.make_ctx(arch, 24, "train", remat=None,
                     compute_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _, _ = M.forward(prog, jnp.asarray(toks), arch, ctx)
    pos = list(range(24))
    got = R.served_logits(cfg, SEED, [list(t) for t in toks], [pos, pos],
                          32)
    # served_logits draws the weights in bfloat16, as they are served
    ref32 = R.logits(ref, _stack(ref, jnp.take(ref["embed"], toks, axis=0),
                                 cfg), cfg)
    np.testing.assert_allclose(ref32, want, atol=2e-5, rtol=1e-4)
    assert np.abs(np.stack(got) - want).max() < 0.05


def _stack(params, x, cfg):
    for i in range(cfg["n_layers"]):
        x = R.layer(jax.tree.map(lambda a: a[i], params["layers"]), x, cfg)
    return x


def test_three_steps_match_the_program_in_float32():
    from repro.configs.base import ArchConfig
    from repro.launch.train import init_train
    from repro.train.optimizer import OptimizerConfig
    from repro.train.train_step import TrainConfig

    cfg = TINY["configs"]["tiny"]
    data = MarkovStream(SEED, 64, 16, 2, 2.5)
    batches = [data.batch_at(i) for i in range(3)]
    ocfg = OptimizerConfig(lr=3e-3, warmup_steps=5, total_steps=10,
                           weight_decay=0.0)
    step, params, opt = init_train(
        ArchConfig(**cfg), TrainConfig(compute_dtype="float32", remat="none"),
        ocfg, seed=SEED)
    start = jax.tree.map(jnp.copy, params)
    losses = []
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            params, opt, m = step(params, opt, jax.tree.map(jnp.asarray, b))
            losses.append(float(m["loss"]))
            if i == 0:
                mu1 = R.leaf_norms(opt["mu"])
    ref_losses, ref_grad, ref_change = R.train_steps(
        cfg, SEED, batches, lr=3e-3, warmup=5, total=10)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    for k, v in ref_grad.items():
        assert abs(mu1[k] / 0.1 - v) <= 1e-4 * max(v, 1e-6), k
    change = R.leaf_norms(jax.tree.map(jnp.subtract, params, start))
    for k, v in ref_change.items():
        assert abs(change[k] - v) <= 1e-3 * max(v, 1e-6), k
