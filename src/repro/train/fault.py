"""Fault tolerance: checkpoint/restart supervision + straggler watchdog.

``TrainSupervisor`` wraps a step function with (a) periodic checkpointing
through the data lake, (b) automatic restore-and-continue on failures
(injectable for tests; on a real pod this is the coordinator restart path),
and (c) a step-time watchdog implementing the paper's straggler policy at
training-step granularity (a step slower than ``straggler_factor`` x the
running median is flagged; on real fleets the launcher would reschedule the
slow host — here we record + expose the signal).

Scheduler preemption ties in here: a checkpoint-aware preemption
(``Scheduler.preempt``) delivers a cooperative signal through the
runner's ``Job.preempt_flag``; ``preemption_hook(job)`` turns that flag
into the ``JobPreempted`` the supervisor (or the agent) already handles,
so a preempted training job stops at a step boundary with its latest
checkpoint saved and the relaunch restores via elastic restore instead
of restarting from step 0. ``JobPreempted`` itself lives in
``core/engine/lifecycle.py`` (the engine must recognize it without
importing the jax-backed train stack) and is re-exported here for
backwards compatibility.
"""
from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Callable, Optional

import jax

from repro.core.engine.lifecycle import (  # noqa: F401 (re-exports)
    JobPreempted, TransientJobError)
from repro.core.trace import span
from repro.train.checkpoints import CheckpointManager


def preemption_hook(job) -> Callable[[int], None]:
    """A ``TrainSupervisor.run(failure_hook=...)`` adapter for the
    engine's cooperative checkpoint signal: raises ``JobPreempted`` at
    the next step boundary once the scheduler preempts ``job``. The
    preemption-capable runners treat the raise as a hand-back (the job
    re-queues and resumes from its last checkpoint), not a failure.

    Create the hook at the *start* of each incarnation (inside the job
    fn): it captures the incarnation's epoch, so a worker superseded by
    a relaunch still observes its preemption even though the relaunch
    installed a fresh (unset) ``preempt_flag`` on the shared Job —
    polling the flag alone would race that replacement and miss the
    signal."""
    epoch0 = getattr(job, "epoch", 0)

    def hook(step: int) -> None:
        flag = getattr(job, "preempt_flag", None)
        if getattr(job, "epoch", 0) != epoch0 or \
                (flag is not None and flag.is_set()):
            exc = JobPreempted(
                f"{job.job_id} preempted at step {step}")
            # external (scheduler-driven) preemptions must propagate out
            # of the supervisor — the process hands capacity back and the
            # *relaunch* restores; restarting in-process would keep the
            # revoked reservation busy
            exc.external = True
            raise exc
    return hook


def gang_resize_hook(job) -> Callable[[int], None]:
    """A ``failure_hook`` adapter for elastic gang shrink-to-k.

    When the scheduler shrinks a resizable gang (``Scheduler.shrink_gang``
    lowers ``job.gang_pods`` without preempting), the training process
    keeps its reservation — it just lost pods. The right reaction is an
    *in-process* re-mesh: raise a non-external ``JobPreempted`` so
    ``TrainSupervisor.run`` restores the latest checkpoint onto the
    shrunken mesh (``CheckpointManager.restore`` reshards onto any mesh)
    and continues, rather than handing the surviving capacity back.

    The hook tracks the last width it acted on, so each shrink fires
    exactly once; compose with :func:`preemption_hook` when the job also
    needs the hand-back path::

        pre, res = preemption_hook(job), gang_resize_hook(job)
        def hook(step):
            pre(step); res(step)
    """
    state = {"w": getattr(job, "gang_pods", None)}

    def hook(step: int) -> None:
        w = getattr(job, "gang_pods", None)
        if w is not None and state["w"] is not None and w < state["w"]:
            state["w"] = w
            raise JobPreempted(
                f"{job.job_id} gang resized to {w} pods at step {step}")
        state["w"] = w
    return hook


def _ready(tree) -> bool:
    """True when every array of ``tree`` has been computed."""
    return all(getattr(x, "is_ready", lambda: True)()
               for x in jax.tree.leaves(tree))


@dataclasses.dataclass
class SupervisorReport:
    steps_run: int = 0
    restarts: int = 0
    checkpoints: int = 0
    straggler_steps: list = dataclasses.field(default_factory=list)
    final_step: int = 0
    # seconds between successive step completions, one per step run
    step_s: list = dataclasses.field(default_factory=list)
    save_s: list = dataclasses.field(default_factory=list)   # per save
    # the step's counters (its metrics named "moe/..."), one per step run
    counters: dict = dataclasses.field(default_factory=dict)

    def counter_means(self) -> dict:
        return {k: statistics.mean(v) for k, v in self.counters.items()}


class TrainSupervisor:
    def __init__(self, ckpt: CheckpointManager, *, save_every: int = 10,
                 straggler_factor: float = 3.0, max_restarts: int = 10):
        self.ckpt = ckpt
        self.save_every = save_every
        self.straggler_factor = straggler_factor
        self.max_restarts = max_restarts

    def run(self, step_fn: Callable, state: dict, n_steps: int,
            batch_fn: Callable[[int], dict],
            failure_hook: Optional[Callable[[int], None]] = None,
            time_fn: Callable[[], float] = time.perf_counter,
            ) -> tuple[dict, SupervisorReport]:
        """state: {"params":..., "opt":..., "step": int}.

        Step ``i`` is dispatched before the host waits on step ``i-1``'s
        metrics (``train/wait``), so one step stays queued on the device
        while the host builds the next batch. A step's time is the
        interval between its completion and the previous one's, seen as
        soon as the host can tell: before the next dispatch if the step is
        already done (the host is the bottleneck), else when the wait
        returns. The straggler policy reads these intervals."""
        report = SupervisorReport()
        step = state["step"]
        pending = None           # (step, metrics) still in flight
        last = time_fn()         # the previous completion

        def complete(i, metrics, t=None):
            nonlocal last
            if t is None:
                with span("train/wait"):
                    jax.block_until_ready(metrics)
                t = time_fn()
            dt, last = t - last, t
            if len(report.step_s) >= 3 and dt > self.straggler_factor * \
                    statistics.median(report.step_s):
                report.straggler_steps.append(i)
            report.step_s.append(dt)
            for k, v in metrics.items():
                if k.startswith("moe/"):
                    report.counters.setdefault(k, []).append(float(v))

        while step < n_steps:
            try:
                if failure_hook is not None:
                    failure_hook(step)       # may raise JobPreempted
                done_at = time_fn() if pending is not None and \
                    _ready(pending[1]) else None
                with span("train/dispatch"):
                    params, opt, metrics = step_fn(
                        state["params"], state["opt"], batch_fn(step))
                if pending is not None:
                    complete(*pending, t=done_at)
                pending = (step, metrics)
                state = {"params": params, "opt": opt, "step": step + 1}
                report.steps_run += 1
                step += 1
                if step % self.save_every == 0 or step == n_steps:
                    complete(*pending)
                    pending = None
                    t0 = time.perf_counter()
                    self.ckpt.save(step, state["params"], state["opt"],
                                   extra={"loss": float(metrics["loss"])})
                    report.save_s.append(time.perf_counter() - t0)
                    report.checkpoints += 1
                    last = time_fn()     # the save is no step's time
            except JobPreempted as e:
                if getattr(e, "external", False):
                    raise   # scheduler preemption: hand back the slot;
                            # the relaunch restores from the checkpoint
                if pending is not None:
                    complete(*pending)
                    pending = None
                report.restarts += 1
                if report.restarts > self.max_restarts:
                    raise
                restored, ck_step = self._restore_or_initial(state)
                state = restored
                step = ck_step
                last = time_fn()
        report.final_step = step
        return state, report

    def _restore_or_initial(self, template_state):
        last = self.ckpt.latest_step()
        if last is None:
            return {"params": template_state["params"],
                    "opt": template_state["opt"], "step": 0}, 0
        st, step = self.ckpt.restore({"params": template_state["params"],
                                      "opt": template_state["opt"]})
        return {"params": st["params"], "opt": st["opt"], "step": step}, step
