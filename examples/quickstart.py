"""Quickstart: train a reduced-config model end-to-end on CPU with the full
production stack — data pipeline, AdamW, remat, datalake-versioned
checkpoints, fault-tolerant supervision, provenance.

    PYTHONPATH=src python examples/quickstart.py [--arch olmo-1b] [--steps 30]
"""
import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro.configs.base import get_arch, list_archs
from repro.core.acai import AcaiEngine, AcaiProject
from repro.core.engine.registry import JobSpec
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.models import model as M
from repro.train.checkpoints import CheckpointManager
from repro.train.fault import TrainSupervisor
from repro.train.optimizer import OptimizerConfig, init_opt_state
from repro.train.train_step import TrainConfig, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=list_archs())
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()

    cfg = get_arch(args.arch).reduced()
    print(f"arch={args.arch} (reduced: {cfg.n_layers}L d={cfg.d_model}, "
          f"{cfg.n_params():,} params)")

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tcfg = TrainConfig(remat="full")
    ocfg = OptimizerConfig(lr=3e-3, warmup_steps=5, total_steps=args.steps,
                           weight_decay=0.0)
    step = jax.jit(make_train_step(cfg, tcfg, ocfg))
    opt = init_opt_state(params)
    pipe = TokenPipeline(DataConfig(vocab_size=32, seq_len=32,
                                    global_batch=16, markov_temp=2.5), cfg)

    workdir = tempfile.mkdtemp(prefix="acai-quickstart-")
    project = AcaiProject("quickstart", workdir)
    data_ref = pipe.register(project, "synthetic-markov", creator="you")
    ckpt = CheckpointManager(project, "quickstart-run")
    sup = TrainSupervisor(ckpt, save_every=10)

    def batch_fn(i):
        return jax.tree.map(jnp.asarray, pipe.batch_at(i))

    state, report = sup.run(step, {"params": params, "opt": opt, "step": 0},
                            args.steps, batch_fn)
    print(f"ran {report.steps_run} steps, {report.checkpoints} checkpoints,"
          f" {report.restarts} restarts")

    # the checkpoint is a versioned fileset with metadata + provenance
    latest = ckpt.latest_step()
    restored, rstep = ckpt.restore({"params": state["params"],
                                    "opt": state["opt"]})
    print(f"latest checkpoint step={latest}; restored step={rstep}")
    print("datalake filesets:", project.filesets.list_sets())
    ids = project.metadata.find(kind="checkpoint")
    print("checkpoint metadata:", {i: project.metadata.get(i).get('loss')
                                   for i in ids[-2:]})

    # evaluation as a platform job: submit returns a JobHandle future and
    # .result() resolves it — no run_all(), no manual sequencing
    eng = AcaiEngine(datalake=project, workroot=workdir + "/jobs")

    def eval_job(wd, job):
        n_params = sum(p.size for p in jax.tree.leaves(restored["params"]))
        print(f"[[acai:eval_params={n_params},ckpt_step={rstep}]]")
        return {"params": int(n_params)}

    handle = eng.submit(JobSpec(name="eval", project="quickstart",
                                user="you", fn=eval_job,
                                resources={"vcpu": 1, "mem_mb": 512}))
    print(f"eval job {handle.job_id}: {handle.result()['params']:,} params "
          f"verified from checkpoint step {rstep}")


if __name__ == "__main__":
    main()
