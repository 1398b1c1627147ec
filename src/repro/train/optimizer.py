"""AdamW + global-norm clipping + cosine schedule, from scratch (no optax).

Optimizer state is a pytree shaped like the params; ``opt_state_specs``
derives ZeRO-1 sharding (first moments/second moments additionally sharded
over the data axis when a dimension divides evenly) — the classic
distributed-optimizer memory saving.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    lr: float = 3e-4
    betas: tuple[float, float] = (0.9, 0.95)
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


def schedule(cfg: OptimizerConfig, step):
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1),
                    0.0, 1.0)
    cos = 0.5 * (1 + jnp.cos(jnp.pi * prog))
    frac = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * cos
    return cfg.lr * warm * frac


def init_opt_state(params, master_weights: bool = False):
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    state = {"mu": zeros,
             "nu": jax.tree.map(jnp.zeros_like, zeros),
             "step": jnp.zeros((), jnp.int32)}
    if master_weights:
        # params live in bf16 (collectives/matmuls stream bf16); the fp32
        # truth lives here, sharded like the moments (ZeRO)
        state["master"] = jax.tree.map(
            lambda p: p.astype(jnp.float32), params)
    return state


# Leaves AdamW does not update: an MoE router's correction bias picks
# experts and takes no gradient (DeepSeek-V3 noaux_tc; the rule that moves
# it in published training lies outside the optimizer).
FROZEN = ("router_bias",)


def trainable(params):
    """A tree of bools shaped like ``params``: False for FROZEN leaves."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) not in FROZEN, params)


def global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32)))
              for g in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def adamw_update(cfg: OptimizerConfig, params, grads, opt_state):
    """Returns (new_params, new_opt_state, metrics). With a "master" entry
    in opt_state the update is computed on the fp32 masters and params are
    re-emitted at their storage dtype (bf16 mixed-precision training)."""
    step = opt_state["step"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
    lr = schedule(cfg, step)
    b1, b2 = cfg.betas
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)
    masters = opt_state.get("master")
    base = masters if masters is not None else params

    def upd(p, out_dtype, train, g, mu, nu):
        if not train:
            return p.astype(out_dtype), p.astype(jnp.float32), mu, nu
        g = g.astype(jnp.float32) * scale
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * jnp.square(g)
        mhat = mu / bc1
        nhat = nu / bc2
        delta = mhat / (jnp.sqrt(nhat) + cfg.eps)
        if p.ndim >= 2:                       # decoupled WD on matrices only
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        new32 = p.astype(jnp.float32) - lr * delta
        return new32.astype(out_dtype), new32, mu, nu

    dtypes = jax.tree.map(lambda p: p.dtype, params)
    out = jax.tree.map(upd, base, dtypes, trainable(params), grads,
                       opt_state["mu"], opt_state["nu"])
    pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                  is_leaf=lambda t: isinstance(t, tuple))
    new_params = pick(0)
    new_state = {"mu": pick(2), "nu": pick(3), "step": step}
    if masters is not None:
        new_state["master"] = pick(1)
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}


# ---------------------------------------------------------------------------
# ZeRO-1 sharding of the optimizer state
# ---------------------------------------------------------------------------

def opt_state_specs(param_specs, param_shapes, rules=None,
                    zero: bool = True):
    """Derive opt-state PartitionSpecs. With ``zero`` and a 'data' axis in
    the rules, moments get one additional dim sharded over data (ZeRO-1)."""
    from repro.sharding.rules import current_rules
    rules = rules or current_rules()
    zero_axes = rules.table.get("zero", ()) if (rules and zero) else ()
    zero_size = 1
    if rules and zero_axes:
        zero_size = int(rules.mesh.shape[zero_axes[0]])

    def one(spec, shape):
        if not zero_axes or zero_size <= 1 or shape is None:
            return spec
        flat_axes = []
        for entry in spec:
            flat_axes.extend(entry if isinstance(entry, tuple) else [entry])
        if zero_axes[0] in flat_axes:      # FSDP params: already data-sharded
            return spec
        parts = list(spec) + [None] * (len(shape.shape) - len(spec))
        for i, (ax, dim) in enumerate(zip(parts, shape.shape)):
            if ax is None and dim % zero_size == 0 and dim >= zero_size:
                parts[i] = zero_axes[0]
                return P(*parts)
        return spec

    moment_specs = jax.tree.map(one, param_specs, param_shapes,
                                is_leaf=lambda x: isinstance(x, P))
    return {"mu": moment_specs, "nu": moment_specs, "step": P()}
