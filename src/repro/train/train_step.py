"""Train-step factory: loss -> (micro-batched) grads -> compression hook ->
AdamW update. The returned function is pure and jit/pjit-able; the launcher
binds shardings."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import model as M
from repro.train import compression as C
from repro.train.optimizer import (OptimizerConfig, adamw_update,
                                   init_opt_state)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    remat: str = "full"              # none | full | dots
    attn_impl: str = "xla"           # xla | xla-bf16-logits (the Pallas
    # flash kernel has no VJP, so make_train_step refuses it)
    grad_compression: Optional[str] = None    # None | bf16 | int8
    compute_dtype: str = "bfloat16"
    # cast params once per step BEFORE the layer scan: FSDP gathers then
    # move bf16 instead of fp32 master shards (halves gather bytes)
    param_stream_dtype: Optional[str] = None   # None | bfloat16
    # store params in bf16 with fp32 masters inside the optimizer state
    # (production mixed precision; gathers/matmuls stream bf16 natively)
    master_weights: bool = False


def make_loss_fn(cfg: ArchConfig, tcfg: TrainConfig):
    cd = jnp.bfloat16 if tcfg.compute_dtype == "bfloat16" else jnp.float32

    def loss_fn(params, batch):
        if tcfg.param_stream_dtype == "bfloat16":
            params = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16)
                if p.dtype == jnp.float32 else p, params)
        seq = batch["tokens"].shape[1]
        ctx = M.make_ctx(cfg, seq, "train", attn_impl=tcfg.attn_impl,
                         remat=tcfg.remat, vision=batch.get("vision"),
                         compute_dtype=cd)
        return M.loss_fn(params, batch, cfg, ctx)

    return loss_fn


def make_train_step(cfg: ArchConfig, tcfg: TrainConfig,
                    ocfg: OptimizerConfig):
    if tcfg.attn_impl.startswith("pallas"):
        raise ValueError(
            f"attn_impl={tcfg.attn_impl!r} cannot train: the Pallas flash "
            f"attention kernel defines no VJP, so jax.grad cannot "
            f"differentiate it; train with attn_impl='xla'")
    loss_fn = make_loss_fn(cfg, tcfg)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if tcfg.microbatches <= 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads

        k = tcfg.microbatches
        micro = jax.tree.map(
            lambda a: a.reshape((k, a.shape[0] // k) + a.shape[1:]), batch)

        def body(acc, mb):
            (loss, metrics), grads = grad_fn(params, mb)
            acc = jax.tree.map(jnp.add, acc,
                               {"loss": loss, "grads": grads})
            return acc, metrics

        zero = {"loss": jnp.zeros((), jnp.float32),
                "grads": jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)}
        acc, metrics = jax.lax.scan(body, zero, micro)
        grads = jax.tree.map(lambda g: g / k, acc["grads"])
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return acc["loss"] / k, metrics, grads

    def train_step(params, opt_state, batch):
        loss, metrics, grads = compute_grads(params, batch)
        if tcfg.grad_compression:
            grads, new_res = C.compress_grads_with_feedback(
                grads, opt_state["residuals"], tcfg.grad_compression)
        with jax.named_scope("optimizer"):
            params, new_opt, opt_metrics = adamw_update(
                ocfg, params, grads,
                {k: v for k, v in opt_state.items() if k != "residuals"})
        if tcfg.grad_compression:
            new_opt["residuals"] = new_res
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        return params, new_opt, metrics

    return train_step


def make_opt_state(params, tcfg: TrainConfig):
    state = init_opt_state(params, master_weights=tcfg.master_weights)
    if tcfg.grad_compression:
        state["residuals"] = C.init_residuals(params)
    return state
