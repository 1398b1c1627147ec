"""The program's own spans, and the op-name paths of its device operations,
in a run's profiler trace.

The program marks its phases with ``repro.core.trace.span``: host
annotations named ``acai/<phase>`` on the device trace's clock (the names
are listed in ``docs/engine.md``). ``trace_reduce.load_events`` keeps
only the benchmark's ``bench/`` spans, so this module reads the run's
``.xplane.pb`` once more and keeps what it finds in ``run.state``.

The op-name path of an XLA operation (its ``jax.named_scope`` names, as
``jit(serve_step)/.../cache_insert/mul``) is the ``tf_op`` stat, which
the TPU profiler keeps on the operation's event metadata.
``jax.profiler.ProfileData`` shows only the events' own stats, so
``op_paths`` reads the metadata from the file's protobuf encoding.

A commit whose program records no spans or scopes gives none here, and
every reader built on them returns None.
"""
from __future__ import annotations

import bisect
import glob
import os

from bench import trace_reduce as T

PREFIX = "acai/"
OP_PATH_STAT = "tf_op"


def newest_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_spans(path: str) -> list[dict]:
    """The program's host spans in the trace file ``path``, as event
    records like ``trace_reduce``'s (plane, line, name, start, duration)."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if T.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append({"plane": plane.name, "line": line.name,
                                "name": ev.name,
                                "start_ns": float(ev.start_ns),
                                "dur_ns": float(ev.duration_ns)})
    return out


# -- the protobuf encoding of an XSpace, as far as op_paths reads it ------
# XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 and
# stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata: name = 2,
# stats = 5; XStatMetadata: name = 2; XStat: metadata_id = 1,
# str_value = 5, ref_value = 7 (the name of another stat metadata).

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """(field number, value) of one message in ``buf[lo:hi]``: an int for
    a varint, (start, end) of the payload for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = (i, i + n), i + n
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield field, value


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    key, value = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane_op_paths(buf, lo: int, hi: int):
    name, events, stat_names = "", [], {}
    for f, v in _fields(buf, lo, hi):
        if f == 2:
            name = _text(buf, v)
        elif f == 4:
            events.append(_map_entry(buf, v)[1])
        elif f == 5:
            sid, value = _map_entry(buf, v)
            for g, w in _fields(buf, *value):
                if g == 2:
                    stat_names[sid] = _text(buf, w)
    if not T.DEVICE_PLANE.match(name):
        return name, {}
    want = [k for k, n in stat_names.items() if n == OP_PATH_STAT]
    paths: dict[str, str] = {}
    for meta in events:
        op, path = "", None
        for f, v in _fields(buf, *meta):
            if f == 2:
                op = _text(buf, v)
            elif f == 5:
                stat = dict(_fields(buf, *v))
                if stat.get(1) not in want:
                    continue
                if 5 in stat:
                    path = _text(buf, stat[5])
                elif 7 in stat:
                    path = stat_names.get(stat[7])
        if path is not None:
            paths[op] = path
    return name, paths


def op_paths(path: str) -> dict[str, dict[str, str]]:
    """{device plane: {operation event name: op-name path}} from the event
    metadata of the trace file ``path``. On a TPU the name is the HLO
    instruction as text (``%fusion.5 = ... fusion(...), calls=...``), as
    ``trace_reduce`` sees it, and the path ends in ``:``."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for field, value in _fields(buf, 0, len(buf)):
        if field == 1:
            name, paths = _plane_op_paths(buf, *value)
            if paths:
                out[name] = paths
    return out


# -- what the readers share ------------------------------------------------

def of(run) -> dict:
    """{"spans": ..., "op_paths": ...} of the run's trace, read once."""
    if "program_spans" not in run.state:
        path = newest_xplane(str(run.work / "trace"))
        run.state["program_spans"] = {"spans": load_spans(path),
                                      "op_paths": op_paths(path)}
    return run.state["program_spans"]


def durations(run, name: str) -> list[float]:
    """Seconds of each ``acai/<name>`` span inside the traced window."""
    s = run.trace_summary
    return [e["dur_ns"] / 1e9 for e in of(run)["spans"]
            if e["name"] == PREFIX + name and e["start_ns"] >= s["lo"]
            and e["start_ns"] + e["dur_ns"] <= s["hi"]]


def mean_span(run, name: str):
    d = durations(run, name)
    return sum(d) / len(d) if d else None


def per_count(run, names: tuple, counted: str, scale: float = 1.0):
    """Seconds of the ``names`` spans summed, per ``counted`` span."""
    n = len(durations(run, counted))
    if not n:
        return None
    return scale * sum(sum(durations(run, k)) for k in names) / n


def scoped_share(run, function: str, scope: str):
    """Share (%) of ``jit_<function>``'s device time spent in operations
    whose op-name path holds ``scope``, averaged over the chips."""
    s = run.trace_summary
    paths = of(run)["op_paths"]
    shares = []
    for plane in s["planes"]:
        runs = T.union((a, b) for a, b in T.module_runs(
            run.trace_events, plane, function) if a >= s["lo"]
            and b <= s["hi"])
        total = sum(b - a for a, b in runs)
        named = paths.get(plane, {})
        if not total or not named:
            return None
        starts = [a for a, _ in runs]
        scoped = sum(
            _overlap(e["start_ns"], e["start_ns"] + e["dur_ns"], runs, starts)
            for e in T.leaf_ops(run.trace_events, plane)
            if scope in named.get(e["name"], ""))
        shares.append(100.0 * scoped / total)
    return sum(shares) / len(shares) if any(shares) else None


def _overlap(a: float, b: float, intervals, starts) -> float:
    """Length of [a, b] inside the sorted, disjoint ``intervals``, whose
    starts are ``starts``."""
    total, j = 0.0, bisect.bisect_left(starts, b) - 1
    while j >= 0 and intervals[j][1] > a:
        total += min(b, intervals[j][1]) - max(a, intervals[j][0])
        j -= 1
    return total


def name_gaps(gaps, spans, n: int = 10):
    """[[innermost program span open at the gap's midpoint, seconds]] of
    the ``n`` longest ``gaps``, and the share of all the gaps' time that
    no program span covers."""
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        open_ = [sp for sp in spans
                 if sp["start_ns"] <= mid <= sp["start_ns"] + sp["dur_ns"]]
        name = min(open_, key=lambda sp: sp["dur_ns"])["name"] if open_ \
            else "(no span)"
        named.append([name, (e - s) / 1e9])
    cover = T.union((sp["start_ns"], sp["start_ns"] + sp["dur_ns"])
                    for sp in spans)
    starts = [s for s, _ in cover]
    idle = sum(e - s for s, e in gaps)
    covered = sum(_overlap(s, e, cover, starts) for s, e in gaps)
    return named, (1.0 - covered / idle) if idle else 0.0
