"""What the training cells share: warm-up, the recording hooks and the check.

The hooks wrap program classes from outside, without editing them:
``CheckpointManager.save`` gets a host span, and the supervisor that
``launch/train.py`` builds is a subclass whose ``run`` wraps the step
function of one chosen job. For that job it keeps, on the device, the
parameters before the first step, the per-leaf norms of AdamW's first
moment after step 1 (the first clipped gradient times 1 - beta1) and of
the parameters' change over steps 1-3, and the first three batches. The
check recomputes those three steps with the plain reference.
"""
from __future__ import annotations

import statistics
import sys

import numpy as np

from bench import reference as R
from bench.traffic_gen import MarkovStream

BETA1 = 0.9            # the program's AdamW default (train/optimizer.py)
CHECKED_STEPS = 3
# A leaf is compared only where the reference moves it: leaves whose
# first gradient is under a thousandth of the median leaf's move under
# Adam by round-off alone (the norms' empty sentinels, for one).
MOVED = 1e-3


def _jits():
    import jax
    import jax.numpy as jnp

    copy = jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.array(a, copy=True), t))
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32)))) for a in jax.tree.leaves(t)])
    diff = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])
    return copy, norms, diff


class Recorder:
    """Keeps the readings of the one job it is armed for."""

    def __init__(self, fault=None):
        self.copy, self.norms, self.diff = _jits()
        self.fault = fault
        self.armed = False
        self.start = None
        self.tokens: list[np.ndarray] = []
        self.mu_norms = None
        self.change = None
        self.paths = None

    def wrap(self, step_fn):
        import jax
        import jax.numpy as jnp

        calls = [0]

        def wrapped(params, opt, batch):
            i = calls[0]
            calls[0] += 1
            if i == 0:
                self.start = self.copy(params)
                self.paths = R.leaf_paths(params)
            if i < CHECKED_STEPS:
                self.tokens.append(np.asarray(batch["tokens"]))
            if self.fault == "half_batch":
                # the second half of the rows repeats the first: the mean
                # is taken over half the batch, at the same shapes
                batch = jax.tree.map(lambda a: jnp.concatenate(
                    [a[:a.shape[0] // 2]] * 2), batch)
            keep = self.copy((params, opt)) if self.fault == "unchanged" \
                else None
            params, opt, metrics = step_fn(params, opt, batch)
            if keep is not None:
                params, opt = keep
            if i == 0:
                self.mu_norms = self.norms(opt["mu"])
            if i == CHECKED_STEPS - 1:
                self.change = self.diff(params, self.start)
                self.start = None
            return params, opt, metrics
        return wrapped

    def readings(self) -> tuple[dict, dict]:
        grad = {p: float(v) / (1 - BETA1)
                for p, v in zip(self.paths, self.mu_norms)}
        change = {p: float(v) for p, v in zip(self.paths, self.change)}
        return grad, change


def install(run, recorder: Recorder) -> None:
    """Wrap the program's checkpoint save and training supervisor."""
    import repro.launch.train as LT
    from repro.train.checkpoints import CheckpointManager

    save = CheckpointManager.save
    if not hasattr(save, "bench_run"):
        program_save = save

        def save(self, *a, **kw):
            with save.bench_run.spans.span("ckpt_save"):
                return program_save(self, *a, **kw)
        CheckpointManager.save = save
    save.bench_run = run

    base = LT.TrainSupervisor
    while getattr(base, "_bench_base", None) is not None:
        base = base._bench_base

    class RecordingSupervisor(base):
        _bench_base = base

        def run(self, step_fn, state, n_steps, batch_fn, *a, **kw):
            if recorder.armed:
                recorder.armed = False
                step_fn = recorder.wrap(step_fn)
            return super().run(step_fn, state, n_steps, batch_fn, *a, **kw)

    LT.TrainSupervisor = RecordingSupervisor


def arch_config(run):
    from repro.configs.base import ArchConfig

    return ArchConfig(**run.arch)


def stream(run) -> MarkovStream:
    t = run.cell.traffic
    return MarkovStream(run.seed, t["data_vocab"], t["seq_len"],
                        t["global_batch"], t["markov_temp"])


def warm(run, recorder: Recorder, lr: float, total_steps: int) -> None:
    """Compile (or load) every program a job with this learning rate and
    step count runs, and run each once: the optimizer's schedule bakes
    both into the step."""
    import jax
    import jax.numpy as jnp
    from repro.launch.train import init_train
    from repro.train.optimizer import OptimizerConfig
    from repro.train.train_step import TrainConfig

    t = run.cell.traffic
    ocfg = OptimizerConfig(lr=lr, warmup_steps=t["warmup_steps"],
                           total_steps=total_steps, weight_decay=0.0)
    step, params, opt = init_train(arch_config(run),
                                   TrainConfig(remat=t["remat"]), ocfg,
                                   seed=run.seed)
    batch = jax.tree.map(jnp.asarray, stream(run).batch_at(0))
    start = recorder.copy(params)
    params, opt, metrics = step(params, opt, batch)
    float(metrics["loss"])
    [float(v) for v in recorder.norms(opt["mu"])]
    [float(v) for v in recorder.diff(params, start)]
    jax.block_until_ready(recorder.copy((params, opt)))


def train_fn(run, *, run_name: str, steps: int, lr: float, sink: dict):
    """The job's function, as a user submits it: one ``train`` call."""
    from repro.launch.train import train
    from repro.train.train_step import TrainConfig

    t = run.cell.traffic
    project = run.state["project"]

    def fn(workdir, job):
        with run.spans.span("job_fn"):
            res = train(arch_config(run), project, run_name, steps=steps,
                        seq_len=t["seq_len"], global_batch=t["global_batch"],
                        data_vocab=t["data_vocab"], save_every=steps,
                        tcfg=TrainConfig(remat=t["remat"]), lr=lr,
                        seed=run.seed)
            sink["losses"] = res.losses
            sink["steps"] = res.report.steps_run
            sink["checkpoints"] = res.report.checkpoints
        return {"steps": res.report.steps_run}
    return fn


def make_engine(run):
    from repro.core.acai import AcaiEngine, AcaiProject

    project = AcaiProject("bench", run.work / "lake")
    engine = AcaiEngine(datalake=project, workroot=str(run.work / "jobs"),
                        runner="local")
    run.state["project"] = project
    run.state["engine"] = engine
    return engine


def submit(run, name: str, fn) -> bool:
    """Submit ``fn`` as a job and wait; True if it FINISHED."""
    from repro.core.engine.lifecycle import JobState
    from repro.core.engine.registry import JobSpec

    engine = run.state["engine"]
    with run.spans.span("job"):
        handle = engine.submit(JobSpec(name=name, project="bench",
                                       user="bench", fn=fn))
        state = handle.wait()
    if state != JobState.FINISHED:
        print(f"job {name} ended {state.value}: {handle.job.error}",
              file=sys.stderr)
    return state == JobState.FINISHED


def _worst_leaf_gap(got: dict, want: dict, counted) -> float:
    """max over ``counted`` leaves of |got - want| / max(want, median)."""
    med = statistics.median(want[p] for p in counted)
    return max(abs(got[p] - want[p]) / max(want[p], med) for p in counted)


def check(run, *, sink: dict, recorder: Recorder, lr: float,
          total_steps: int) -> list[tuple[str, float, float]]:
    """The comparison with the reference over the recorded job's first
    three steps, each number beside its limit."""
    lim = run.cell.limits
    data = stream(run)
    batches = [data.batch_at(i) for i in range(CHECKED_STEPS)]
    mismatch = sum(int(not np.array_equal(b["tokens"], got))
                   for b, got in zip(batches, recorder.tokens))
    mismatch += CHECKED_STEPS - len(recorder.tokens)
    t = run.cell.traffic
    with run.spans.span("reference"):
        ref = R.train_steps(run.arch, run.seed, batches, lr=lr,
                            warmup=t["warmup_steps"], total=total_steps)
    run.state["reference"] = ref
    run.state["batches"] = batches
    run.state["schedule"] = (lr, t["warmup_steps"], total_steps)
    return compare(sink["losses"][:CHECKED_STEPS], recorder.readings(), ref,
                   mismatch, lim)


def control(run, quant="fp8") -> list[tuple[str, float, float]]:
    """The reference computed at ``quant`` in the program's place, held to
    the same numbers and limits (run after ``check``)."""
    lr, warmup, total = run.state["schedule"]
    losses, grad, change = R.train_steps(
        run.arch, run.seed, run.state["batches"], lr=lr, warmup=warmup,
        total=total, quant=quant)
    return compare(losses, (grad, change), run.state["reference"], 0,
                   run.cell.limits)


def compare(losses, readings, ref, mismatch, lim):
    grad, change = readings
    ref_losses, ref_grad, ref_change = ref
    sized = [p for p in ref_grad if p in grad]
    med = statistics.median(ref_grad[p] for p in sized)
    counted = [p for p in sized if ref_grad[p] >= MOVED * med]
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses))
    return [
        ("data_mismatch", float(mismatch), float(lim["data_mismatch"])),
        ("loss_gap", loss_gap, lim["loss_gap"]),
        ("grad_norm_gap", _worst_leaf_gap(grad, ref_grad, counted),
         lim["grad_norm_gap"]),
        ("param_change_gap", _worst_leaf_gap(change, ref_change, counted),
         lim["param_change_gap"]),
    ]
