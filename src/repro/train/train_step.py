"""Train-step factory: loss -> grads -> AdamW update. The returned function
is pure and jit/pjit-able; the launcher binds shardings. Its optimizer
state is ``optimizer.init_opt_state(params)``."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import model as M
from repro.train.optimizer import OptimizerConfig, adamw_update


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    remat: str = "full"              # none | full | dots
    compute_dtype: str = "bfloat16"


def make_loss_fn(cfg: ArchConfig, tcfg: TrainConfig):
    cd = jnp.bfloat16 if tcfg.compute_dtype == "bfloat16" else jnp.float32

    def loss_fn(params, batch):
        seq = batch["tokens"].shape[1]
        ctx = M.make_ctx(cfg, seq, "train", remat=tcfg.remat,
                         vision=batch.get("vision"), compute_dtype=cd)
        return M.loss_fn(params, batch, cfg, ctx)

    return loss_fn


def make_train_step(cfg: ArchConfig, tcfg: TrainConfig,
                    ocfg: OptimizerConfig):
    grad_fn = jax.value_and_grad(make_loss_fn(cfg, tcfg), has_aux=True)

    def train_step(params, opt_state, batch):
        (_, metrics), grads = grad_fn(params, batch)
        with jax.named_scope("optimizer"):
            params, new_opt, opt_metrics = adamw_update(
                ocfg, params, grads, opt_state)
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        return params, new_opt, metrics

    return train_step
