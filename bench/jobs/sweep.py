"""A learning-rate sweep: short training trials submitted back to back
through ``AcaiEngine``, each a ``launch/train.py`` ``train`` call that ends
with its own checkpoint save into the data lake.

The grid is cycled from an entry drawn from the seed; the number of
trials is ``max(2, round(seconds / trial_s))`` (``pace/<cell>.json``), so
every seed runs the same work. Set-up compiles (or loads) the program of
each learning rate of the grid once, whichever the seed draws: the rate
is a constant inside the optimizer's schedule. The trial whose first
steps are checked is drawn from the seed too.
"""
from __future__ import annotations

from bench import train_common as TC


def _trials(run) -> int:
    return max(2, round(run.seconds / run.cell.pace["trial_s"]))


def _lr(run, i: int) -> float:
    grid = run.cell.traffic["lrs"]
    return grid[(run.seed + i) % len(grid)]


def setup(run) -> None:
    rec = TC.Recorder(run.fault)
    TC.install(run, rec)
    run.state["recorder"] = rec
    TC.make_engine(run)
    steps = run.cell.traffic["trial_steps"]
    for lr in run.cell.traffic["lrs"]:
        TC.warm(run, rec, lr, steps)
    TC.submit(run, "warm-up", lambda wd, job: {})
    run.state["checked"] = (run.seed // 7) % _trials(run)


def window(run) -> dict:
    t = run.cell.traffic
    steps = t["trial_steps"]
    done, failed = 0, 0
    rec = run.state["recorder"]
    for i in range(_trials(run)):
        sink: dict = {}
        if i == run.state["checked"]:
            run.state["sink"] = sink
            rec.armed = True
        with run.spans.span("turnaround"):
            ok = TC.submit(run, f"trial{i}", TC.train_fn(
                run, run_name=f"trial{i}", steps=steps, lr=_lr(run, i),
                sink=sink))
        if ok and sink.get("steps") == steps:
            done += steps
        else:
            failed += 1
    spans = run.spans.of("turnaround")
    tokens = done * t["global_batch"] * t["seq_len"]
    return {"metrics": {"sweep_tokens_per_s":
                        tokens / (spans[-1][1] - spans[0][0])},
            "attempted": _trials(run), "failed": failed}


def release(run) -> None:
    run.state.pop("engine", None)


def check(run) -> list:
    return TC.check(run, sink=run.state["sink"],
                    recorder=run.state["recorder"],
                    lr=_lr(run, run.state["checked"]),
                    total_steps=run.cell.traffic["trial_steps"])
