"""From a profiler trace to busy time, idle gaps and step executions.

``load_events`` flattens the ``.xplane.pb`` that ``jax.profiler`` writes
into plain event records; everything else works on those records, so the
reduction is checked on a small recorded trace (``fixtures/``) with no
chip. On a TPU each chip is a plane ``/device:TPU:<n>``; its ``XLA Ops``
line holds every operation the chip ran, its ``XLA Modules`` line one
event per execution of a compiled program (``jit_<function>(<id>)``).
The benchmark's own host spans are ``TraceAnnotation``s named
``bench/<what>`` on the host plane.
"""
from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench/"


def load_events(trace_dir: str) -> list[dict]:
    """Device op/module events and the benchmark's host spans of the
    newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_SPAN_PREFIX):
                    continue
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def device_planes(events) -> list[str]:
    return sorted({e["plane"] for e in events
                   if DEVICE_PLANE.match(e["plane"])})


def host_spans(events, name: str | None = None) -> list[dict]:
    return [e for e in events if e["name"].startswith(HOST_SPAN_PREFIX)
            and not DEVICE_PLANE.match(e["plane"])
            and (name is None or e["name"] == name)]


def window(events) -> tuple[float, float]:
    """(start, end) in ns of the ``bench/window`` host span."""
    spans = host_spans(events, HOST_SPAN_PREFIX + "window")
    if len(spans) != 1:
        raise ValueError(f"expected one bench/window span, found "
                         f"{len(spans)}")
    s = spans[0]
    return s["start_ns"], s["start_ns"] + s["dur_ns"]


def union(intervals) -> list[tuple[float, float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_intervals(events, plane: str, lo: float, hi: float):
    ivs = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
           if e["plane"] == plane and e["line"] == OPS_LINE]
    return union(_clip(ivs, lo, hi))


def busy_ns(events, plane: str, lo: float, hi: float) -> float:
    return sum(e - s for s, e in op_intervals(events, plane, lo, hi))


def idle_gaps(events, plane: str, lo: float, hi: float):
    """Intervals in [lo, hi] in which ``plane`` ran no operation."""
    gaps, t = [], lo
    for s, e in op_intervals(events, plane, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def module_runs(events, plane: str, function: str):
    """(start, end) of each execution of ``jit_<function>`` on ``plane``."""
    pat = re.compile(rf"^jit_{re.escape(function)}(\(|$)")
    return sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                  for e in events if e["plane"] == plane
                  and e["line"] == MODULES_LINE and pat.match(e["name"]))


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def leaf_ops(events, plane):
    """Op events of ``plane`` that hold no other op: a loop (``while``)
    spans the ops of its body on the same line."""
    ops = sorted((e for e in events
                  if e["plane"] == plane and e["line"] == OPS_LINE),
                 key=lambda e: (e["start_ns"], -e["dur_ns"]))
    return [e for i, e in enumerate(ops)
            if i + 1 == len(ops)
            or ops[i + 1]["start_ns"] >= e["start_ns"] + e["dur_ns"]]


def top_ops(events, planes, lo, hi, n=10):
    """[[op name, seconds]] of the ``n`` operations that took most device
    time, summed over ``planes`` and averaged per plane."""
    tot: dict[str, float] = {}
    for p in planes:
        for e in leaf_ops(events, p):
            d = min(e["start_ns"] + e["dur_ns"], hi) - max(e["start_ns"], lo)
            if d > 0:
                k = op_name(e["name"])
                tot[k] = tot.get(k, 0.0) + d
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / len(planes) / 1e9] for k, v in top]


def named_gaps(events, plane, lo, hi, n=10):
    """[[host span, seconds]] of the ``n`` longest idle gaps of ``plane``,
    each named by the innermost benchmark span open at its midpoint."""
    spans = [s for s in host_spans(events)
             if s["name"] != HOST_SPAN_PREFIX + "window"]
    out = []
    for s, e in sorted(idle_gaps(events, plane, lo, hi),
                       key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        open_ = [sp for sp in spans
                 if sp["start_ns"] <= mid <= sp["start_ns"] + sp["dur_ns"]]
        name = min(open_, key=lambda sp: sp["dur_ns"])["name"] if open_ \
            else HOST_SPAN_PREFIX + "window"
        out.append([name, (e - s) / 1e9])
    return out


def summary(events) -> dict:
    """Window, busy seconds averaged over the chips, and the breakdown."""
    lo, hi = window(events)
    planes = device_planes(events)
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    busy = sum(busy_ns(events, p, lo, hi) for p in planes) / len(planes)
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy / 1e9,
            "planes": planes, "lo": lo, "hi": hi,
            "breakdown": {"device_ops": top_ops(events, planes, lo, hi),
                          "idle_gaps": named_gaps(events, planes[0], lo,
                                                  hi)}}
