"""One run of one cell: set-up, a measured window, the check, one JSON line.

The window drives the entry users call, ``AcaiEngine.submit`` with the
in-process ``LocalRunner``, into ``launch/train.py``'s ``train`` or
``launch/serve.py``'s ``serve``. The harness never calls a jitted step
itself. It records host spans around the calls into each layer, counts
JAX's compilations through ``jax.monitoring``, and with ``trace`` on
records a profiler trace of the window. After the window it reads the
device's memory peak, frees the program's state and runs the cell's
comparison with the plain reference (``reference.py``).
"""
from __future__ import annotations

import dataclasses
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Optional

from bench import spec as S

# JAX times compile-or-load-from-the-persistent-cache as one backend
# compile event; a load also records a cache hit.
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
JIT_SETUP_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                    "/jax/core/compile/jaxpr_to_mlir_module_duration",
                    BACKEND_COMPILE)


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a chip with no published peaks."""


class Spans:
    """Host spans on the host clock (and in the profiler's trace when one
    is recording): name, start, end; kept in memory."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []
        self.tracing = False

    def span(self, name: str):
        return _Span(self, name)

    def of(self, name: str, lo: float = float("-inf"),
           hi: float = float("inf")) -> list[tuple[float, float]]:
        return [(s, e) for n, s, e in self.records
                if n == name and s >= lo and e <= hi]


class _Span:
    def __init__(self, spans: Spans, name: str):
        self.spans, self.name = spans, name
        self.ann = None

    def __enter__(self):
        if self.spans.tracing:
            import jax
            self.ann = jax.profiler.TraceAnnotation("bench/" + self.name)
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        self.spans.records.append((self.name, self.t0, t1))
        return False


class CompileLog:
    """JAX's own tracing, lowering, compile and cache-load durations, with
    the host time each ended at."""

    def __init__(self):
        self.events: list[tuple[str, float, float]] = []

    def install(self):
        import jax

        def on_duration(event, duration, **_):
            if event in JIT_SETUP_EVENTS:
                self.events.append((event, time.perf_counter(), duration))

        def on_event(event, **_):
            if event == CACHE_HIT:
                self.events.append((event, time.perf_counter(), 0.0))
        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def count(self, event: str, lo: float, hi: float) -> int:
        return sum(1 for e, t, _ in self.events if e == event and lo <= t <= hi)

    def backend_compiles(self, lo: float, hi: float) -> int:
        """Programs compiled, not loaded from the persistent cache."""
        return self.count(BACKEND_COMPILE, lo, hi) - self.count(CACHE_HIT,
                                                                lo, hi)

    def seconds(self, lo: float, hi: float) -> float:
        return sum(d for _, t, d in self.events if lo <= t <= hi)


@dataclasses.dataclass
class Run:
    """State of one run, handed to the job kind and the metric readers."""
    cell: S.Cell
    seed: int
    seconds: int
    trace: bool
    work: Path
    fault: Optional[str] = None        # planted fault (tests only)
    spans: Spans = dataclasses.field(default_factory=Spans)
    compiles: CompileLog = dataclasses.field(default_factory=CompileLog)
    device: dict = dataclasses.field(default_factory=dict)
    peaks: dict = dataclasses.field(default_factory=dict)
    state: dict = dataclasses.field(default_factory=dict)
    window: tuple = (0.0, 0.0)         # host clock of the measured window
    trace_summary: Optional[dict] = None
    trace_events: Optional[list] = None

    @property
    def arch(self) -> dict:
        return S.arch_fields(self.cell.config)


def check_device(chips: int, require_tpu: bool = True) -> tuple[dict, dict]:
    import jax

    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": min(len(devs), chips)}
    if not require_tpu:
        return info, {"peak_flops": 1.0, "hbm_bytes_per_s": 1.0}
    if d.platform != "tpu":
        raise NoChip(f"no TPU attached: JAX found {d.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
    try:
        peaks = S.device_peaks(d.device_kind)
    except KeyError as e:
        raise NoChip(str(e)) from None
    return info, peaks


def use_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``.jax_cache`` in the checkout (a fixed path, which the
    program's ``launch/compile_cache.py`` also takes). Every program goes
    in, however fast it compiled, so the window only loads."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(n: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n]]
    return int(max(peaks))


def _reduce_trace(run: Run, trace_dir: Path) -> None:
    from bench import trace_reduce as T

    run.trace_events = T.load_events(str(trace_dir))
    run.trace_summary = T.summary(run.trace_events)


def execute(cell: S.Cell, seed: int, seconds: int, trace: bool, *,
            t_start: float, root: Path = S.ROOT, require_tpu: bool = True,
            fault: Optional[str] = None, after=None) -> dict:
    """Run ``cell`` once; returns the result object (not yet printed).
    ``fault`` plants a fault in the timed path and ``after(run)`` reads
    more from the run once it is checked (the control's readings)."""
    import jax

    work = root / ".bench_work" / cell.name
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    run = Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
              work=work, fault=fault)
    run.device, run.peaks = check_device(cell.chips, require_tpu)
    use_cache(root)
    run.compiles.install()
    job = S.job_module(cell)

    job.setup(run)
    trace_dir = work / "trace"
    if trace:
        run.spans.tracing = True
        # host spans come from TraceAnnotation; the Python call tracer
        # would slow the serving loop and swell the trace
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    with run.spans.span("window"):
        outcome = job.window(run)
    t1 = time.perf_counter()
    run.window = (t0, t1)
    if trace:
        jax.profiler.stop_trace()
        run.spans.tracing = False
    window_compiles = run.compiles.backend_compiles(t0, t1)
    run.device["memory_peak_bytes"] = memory_peak(cell.chips)

    metrics: dict[str, dict] = {}
    if trace:
        _reduce_trace(run, trace_dir)
        run.device["busy_s"] = run.trace_summary["busy_s"]
        run.device["window_s"] = run.trace_summary["window_s"]
        for m in cell.per_layer:
            value = S.metric_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(outcome["metrics"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    job.release(run)
    # the data lake's checkpoints are the bulk of what a run writes
    for entry in work.iterdir():
        if entry != trace_dir:
            shutil.rmtree(entry, ignore_errors=True)
    numbers = job.check(run)
    numbers.append(("window_compiles", float(window_compiles),
                    float(cell.limits["window_compiles"])))
    correct = outcome["failed"] == 0 and all(
        v <= lim for _, v, lim in numbers)
    result: dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(outcome["attempted"]),
        "failed": int(outcome["failed"]),
        "metrics": metrics,
        "device": run.device,
    }
    if trace:
        result["breakdown"] = run.trace_summary["breakdown"]
    result["compared"] = {n: {"value": v, "limit": lim}
                          for n, v, lim in numbers}
    if after is not None:
        after(run)
    print(f"setup: {setup_s:.3f} s, of it JAX tracing, lowering and "
          f"compile-or-load {run.compiles.seconds(t_start, t0):.3f} s; "
          f"backend compiles {run.compiles.backend_compiles(t_start, t0)}",
          file=sys.stderr)
    for name in ("window", "ckpt_save", "reference"):
        for s, e in run.spans.of(name):
            print(f"{name}: {e - s:.3f} s", file=sys.stderr)
    for n, v, lim in numbers:
        print(f"compared {n}: {v!r} limit {lim!r}", file=sys.stderr)
    return result

