"""Arithmetic the per-layer metric files share (``metrics/<name>.py``).

Each reader takes the run and returns a number, or None where the run
holds nothing to read; the harness then leaves the metric out.
"""
from __future__ import annotations

import statistics

from bench import flops as F
from bench import trace_reduce as T


def mean_span(run, name: str):
    spans = run.spans.of(name, *run.window)
    return statistics.mean(e - s for s, e in spans) if spans else None


def engine_overhead(run):
    """Per job: submit to FINISHED, less the time inside the job function."""
    jobs = run.spans.of("job", *run.window)
    fns = run.spans.of("job_fn", *run.window)
    if not jobs or len(jobs) != len(fns):
        return None
    return statistics.mean((j1 - j0) - (f1 - f0)
                           for (j0, j1), (f0, f1) in zip(jobs, fns))


def jit_setup(run):
    """Per job: JAX's tracing, lowering and compile-or-cache-load seconds."""
    jobs = run.spans.of("job", *run.window)
    if not jobs:
        return None
    return statistics.mean(run.compiles.seconds(s, e) for s, e in jobs)


def idle_share(run):
    s = run.trace_summary
    if s is None or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])


def _runs(run, function: str):
    s = run.trace_summary
    if s is None:
        return None
    per_plane = [[(a, b) for a, b in T.module_runs(run.trace_events, p,
                                                    function)
                  if a >= s["lo"] and b <= s["hi"]] for p in s["planes"]]
    if not per_plane[0]:
        return None
    return per_plane


def step_device_s(per_plane) -> float:
    """Device seconds of a program's executions, averaged over chips."""
    return statistics.mean(sum(b - a for a, b in runs)
                           for runs in per_plane) / 1e9


def train_mfu(run):
    per_plane = _runs(run, "train_step")
    if per_plane is None:
        return None
    t = run.cell.traffic
    tokens = t["global_batch"] * t["seq_len"] * len(per_plane[0])
    model_flops = F.train_flops_per_token(run.arch, t["seq_len"]) * tokens
    busy = step_device_s(per_plane) * run.cell.chips * \
        run.peaks["peak_flops"]
    return 100.0 * model_flops / busy


def decode_roofline(run):
    """Least time of each decode step (larger of FLOPs over peak and
    required bytes over bandwidth), summed, over the steps' device time."""
    from bench.traffic_gen import serve_schedule

    per_plane = _runs(run, "serve_step")
    if per_plane is None:
        return None
    t = run.cell.traffic
    lengths = [len(p) for p in run.state["prompts"]]
    least = 0.0
    ticks = 0
    for live in serve_schedule(lengths, t["slots"], t["max_new"]):
        f, b = F.decode_step_cost(run.arch, live)
        least += max(f / run.peaks["peak_flops"],
                     b / run.peaks["hbm_bytes_per_s"])
        ticks += 1
    if ticks != len(per_plane[0]):
        return None
    return 100.0 * least / step_device_s(per_plane)


def decode_host_gap_ms(run):
    """Device idle between the first and last decode step, per step."""
    per_plane = _runs(run, "serve_step")
    if per_plane is None:
        return None
    plane = run.trace_summary["planes"][0]
    runs = per_plane[0]
    lo, hi = runs[0][0], runs[-1][1]
    idle = sum(e - s for s, e in T.idle_gaps(run.trace_events, plane, lo, hi))
    return idle / len(runs) / 1e6
