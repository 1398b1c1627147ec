"""End-to-end training driver.

On a real pod this binds the production mesh + shardings and runs the
supervised loop; on CPU (default) it trains the reduced config so the whole
path — pipeline -> sharded step -> checkpoints -> fault supervision -> ACAI
provenance — is exercised end to end.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 50
    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --full \
        --mesh 16x16           # requires a real 256-device runtime
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import statistics
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs.base import get_arch, list_archs
from repro.core.acai import AcaiProject
from repro.core.trace import span
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.launch.compile_cache import use_compile_cache
from repro.models import model as M
from repro.sharding.mesh import make_mesh
from repro.sharding import rules as SR
from repro.train.checkpoints import CheckpointManager
from repro.train.fault import SupervisorReport, TrainSupervisor
from repro.train.optimizer import OptimizerConfig, opt_state_specs
from repro.train.train_step import (TrainConfig, make_opt_state,
                                    make_train_step)


def build_sharded_train(cfg, tcfg, ocfg, mesh):
    """Production assembly: specs + jit with shardings (used on pods; the
    dry-run lowers exactly this)."""
    rules = SR.AxisRules.for_mesh(mesh)
    SR.set_rules(rules)
    param_shapes = jax.eval_shape(
        functools.partial(M.init_params, cfg), jax.random.PRNGKey(0))
    pspecs = SR.param_specs(cfg, rules, fsdp=True,
                            param_shapes=param_shapes)
    ospecs = opt_state_specs(pspecs, param_shapes, rules)
    step = make_train_step(cfg, tcfg, ocfg)
    named = lambda t: jax.tree.map(
        lambda sp: NamedSharding(mesh, sp), t,
        is_leaf=lambda x: type(x).__name__ == "PartitionSpec")
    pshard, oshard = named(pspecs), named(ospecs)
    return jax.jit(step, in_shardings=(pshard, oshard, None),
                   out_shardings=(pshard, oshard, None),
                   donate_argnums=(0, 1)), pshard, oshard


@dataclasses.dataclass
class TrainResult:
    state: dict                    # {"params", "opt", "step"}
    report: SupervisorReport
    losses: list[float]            # per step run, in order
    ckpt: CheckpointManager


def init_train(cfg, tcfg: TrainConfig, ocfg: OptimizerConfig, *,
               mesh=None, seed: int = 0):
    """The jitted train step and its freshly initialised (params, opt).
    With ``mesh`` the step is the sharded production assembly, and the
    state is born sharded rather than gathered whole on one device."""
    pshard = oshard = None
    if mesh is not None:
        step, pshard, oshard = build_sharded_train(cfg, tcfg, ocfg, mesh)
    else:
        step = jax.jit(make_train_step(cfg, tcfg, ocfg),
                       donate_argnums=(0, 1))
    params = jax.jit(functools.partial(M.init_params, cfg),
                     out_shardings=pshard)(jax.random.PRNGKey(seed))
    opt = jax.jit(functools.partial(make_opt_state, tcfg=tcfg),
                  out_shardings=oshard)(params)
    return step, params, opt


def train(cfg, project: AcaiProject, run: str, *, steps: int, seq_len: int,
          global_batch: int, data_vocab: int, save_every: int,
          tcfg: TrainConfig = TrainConfig(), lr: float = 3e-3,
          mesh=None, seed: int = 0) -> TrainResult:
    """Supervised training of ``cfg`` on the seeded synthetic stream
    (``data_vocab`` tokens), checkpointing into ``project``'s data lake."""
    ocfg = OptimizerConfig(lr=lr, warmup_steps=5, total_steps=steps,
                           weight_decay=0.0)
    with span("train/init"):
        step, params, opt = init_train(cfg, tcfg, ocfg, mesh=mesh,
                                       seed=seed)
    pipe = TokenPipeline(DataConfig(
        seed=seed, vocab_size=data_vocab, seq_len=seq_len,
        global_batch=global_batch, markov_temp=2.5), cfg)
    with span("train/register"):
        pipe.register(project, f"{run}-data", creator="trainer")
    ckpt = CheckpointManager(project, run)
    sup = TrainSupervisor(ckpt, save_every=save_every)
    losses = []

    def logged_step(params, opt, batch):
        params, opt, metrics = step(params, opt, batch)
        losses.append(metrics["loss"])
        return params, opt, metrics

    def batch_fn(i):
        return jax.tree.map(jnp.asarray, pipe.batch_at(i))

    with span("train/steps"):
        state, report = sup.run(logged_step, {"params": params, "opt": opt,
                                              "step": 0}, steps, batch_fn)
    if report.step_s:
        # the engine's log parser attaches these to the job's metadata
        counters = "".join(f",{k}={v}"
                           for k, v in report.counter_means().items())
        print(f"[[acai:step_s_median={statistics.median(report.step_s)},"
              f"straggler_steps={len(report.straggler_steps)},"
              f"ckpt_save_s={sum(report.save_s)}{counters}]]")
    return TrainResult(state, report, [float(x) for x in losses], ckpt)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=list_archs())
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--full", action="store_true",
                    help="full config (needs a real accelerator mesh)")
    ap.add_argument("--mesh", default=None, help="e.g. 16x16")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--save-every", type=int, default=20)
    ap.add_argument("--workdir", default="/tmp/acai-train")
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_arch(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        mesh = make_mesh(shape, ("data", "model")[:len(shape)])
    res = train(cfg, AcaiProject("train", Path(args.workdir)),
                f"{args.arch}-run", steps=args.steps, seq_len=args.seq_len,
                global_batch=args.global_batch,
                data_vocab=min(cfg.vocab_size, 64),
                save_every=args.save_every,
                tcfg=TrainConfig(remat=args.remat), lr=args.lr, mesh=mesh)
    print(f"done: {res.report.steps_run} steps, {res.report.checkpoints} "
          f"ckpts, latest={res.ckpt.latest_step()}")


if __name__ == "__main__":
    main()
