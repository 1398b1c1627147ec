"""One training job per run: ``AcaiEngine.submit`` -> ``LocalRunner`` ->
``launch/train.py`` ``train``, ending with the job's one checkpoint save.

Its step count is ``round(seconds x steps_per_s)`` (``pace/<cell>.json``),
fixed before the run: the optimizer's cosine schedule bakes the count into
the step, so set-up warms exactly that program.
"""
from __future__ import annotations

from bench import train_common as TC


def _steps(run) -> int:
    return max(TC.CHECKED_STEPS + 1,
               round(run.seconds * run.cell.pace["steps_per_s"]))


def setup(run) -> None:
    rec = TC.Recorder(run.fault)
    TC.install(run, rec)
    run.state["recorder"] = rec
    TC.make_engine(run)
    TC.warm(run, rec, run.cell.traffic["lr"], _steps(run))
    TC.submit(run, "warm-up", lambda wd, job: {})


def window(run) -> dict:
    steps, t = _steps(run), run.cell.traffic
    sink: dict = {}
    run.state["sink"] = sink
    run.state["recorder"].armed = True
    with run.spans.span("turnaround"):
        ok = TC.submit(run, "train", TC.train_fn(
            run, run_name="train", steps=steps, lr=t["lr"], sink=sink))
    (t0, t1), = run.spans.of("turnaround")
    tokens = sink.get("steps", 0) * t["global_batch"] * t["seq_len"]
    return {"metrics": {"train_tokens_per_s": tokens / (t1 - t0)},
            "attempted": 1, "failed": 0 if ok and sink.get("steps") == steps
            else 1}


def release(run) -> None:
    run.state.pop("engine", None)


def check(run) -> list:
    return TC.check(run, sink=run.state["sink"],
                    recorder=run.state["recorder"],
                    lr=run.cell.traffic["lr"], total_steps=_steps(run))
