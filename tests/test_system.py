"""End-to-end behaviour of the whole system: the paper's workflow (data ->
jobs -> provenance -> provisioning) wrapped around real JAX training, plus
the (arch x shape) applicability matrix."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import get_arch, list_archs
from repro.configs.shapes import SHAPES, applicable, cells
from repro.core.acai import AcaiPlatform
from repro.core.engine.lifecycle import JobState
from repro.core.engine.registry import JobSpec


def test_cell_matrix():
    archs = [get_arch(a) for a in list_archs()]
    all_cells = cells(archs)
    assert len(all_cells) == 44                      # 11 archs x 4 shapes
    runnable = [c for c in all_cells if c[2]]
    skipped = [c for c in all_cells if not c[2]]
    assert len(runnable) == 34
    assert len(skipped) == 10
    # long_500k needs a sub-quadratic arch; latent attention (MLA) has
    # no decode cache yet
    assert all(c[1].name == "long_500k" or (c[0].mla and c[1].kind ==
                                            "decode") for c in skipped)
    assert all(not c[0].subquadratic for c in skipped)
    # sub-quadratic archs DO run long_500k
    for name in ("rwkv6-7b", "zamba2-7b"):
        assert applicable(get_arch(name), SHAPES["long_500k"])[0]


def test_full_acai_training_workflow(tmp_path):
    """The usability-study loop end to end with a real (tiny) LM train job:
    upload -> fileset -> job through the engine -> checkpoint fileset with
    provenance -> metadata query finds the best run."""
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.models import model as M
    from repro.train.checkpoints import CheckpointManager
    from repro.train.optimizer import OptimizerConfig, init_opt_state
    from repro.train.train_step import TrainConfig, make_train_step

    plat = AcaiPlatform(tmp_path)
    admin = plat.create_project(plat.admin_token, "e2e")
    proj = plat.project(admin)
    proj.upload("/data/dataset.json", b'{"seed": 7}', creator="e2e")
    proj.create_file_set("TrainData", ["/data/dataset.json"], creator="e2e")

    def train_job(workdir, job):
        lr = job.spec.args["lr"]
        cfg = get_arch("olmo-1b").reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(0))
        tcfg = TrainConfig()
        step = jax.jit(make_train_step(
            cfg, tcfg, OptimizerConfig(lr=lr, warmup_steps=2,
                                       weight_decay=0.0)))
        opt = init_opt_state(params)
        pipe = TokenPipeline(DataConfig(vocab_size=32, seq_len=16,
                                        global_batch=8, markov_temp=2.5),
                             cfg)
        loss = None
        for i in range(8):
            batch = jax.tree.map(jnp.asarray, pipe.batch_at(i))
            params, opt, metrics = step(params, opt, batch)
            loss = float(metrics["loss"])
        ckpt = CheckpointManager(proj, f"run-lr{lr}")
        ckpt.save(8, params, extra={"final_loss": loss},
                  job_id=job.job_id, input_fileset="TrainData")
        print(f"[[acai:final_loss={loss}]]")

    jobs = [plat.submit_job(admin, JobSpec(
        name=f"train-lr{lr}", project="", user="", fn=train_job,
        input_fileset="TrainData", args={"lr": lr},
        resources={"vcpu": 2, "mem_mb": 2048})) for lr in (3e-3, 1e-4)]
    eng = plat.engine(admin)
    for j in jobs:
        assert eng.registry.get(j.job_id).state == JobState.FINISHED, \
            eng.registry.get(j.job_id).error

    # metadata: the higher-lr run should have learned more in 8 steps
    best = proj.metadata.find_min("final_loss", kind="job")
    assert eng.registry.get(best).spec.args["lr"] == pytest.approx(3e-3)

    # provenance: checkpoint filesets trace back to the dataset
    back = proj.provenance.backward("run-lr0.003-ckpt:1")
    assert any(src == "TrainData:1" for src, _ in back)
    # and the checkpoint is restorable
    cfg = get_arch("olmo-1b").reduced()
    template = M.init_params(cfg, jax.random.PRNGKey(0))
    state, step_no = CheckpointManager(proj, "run-lr0.003").restore(
        {"params": template})
    assert step_no == 8
    assert jax.tree.structure(state["params"]) == \
        jax.tree.structure(template)
