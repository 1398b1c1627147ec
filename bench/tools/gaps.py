#!/usr/bin/env python3
"""The device's idle gaps in a traced run, named by the program's spans.

    python3 bench/tools/gaps.py <trace dir>

``<trace dir>`` is where a ``--trace 1`` run wrote its profiler trace
(``.bench_work/<cell>/trace``). Inside the ``bench/window`` span, the
tool prints the ten longest gaps in which the first chip ran no
operation, each named by the innermost ``acai/`` span open at its
midpoint, then the idle seconds and the share of them that no ``acai/``
span covers. The last line of stdout is the same as JSON.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.strip().split("\n\n")[1], file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT))
    from bench import program_spans as P
    from bench import trace_reduce as T

    events = T.load_events(args[0])
    lo, hi = T.window(events)
    planes = T.device_planes(events)
    if not planes:
        print("the trace holds no TPU device plane", file=sys.stderr)
        return 1
    plane = planes[0]
    gaps = T.idle_gaps(events, plane, lo, hi)
    spans = P.load_spans(P.newest_xplane(args[0]))
    named, uncovered = P.name_gaps(gaps, spans)
    idle = sum(e - s for s, e in gaps) / 1e9
    for name, seconds in named:
        print(f"{seconds:12.6f} s  {name}")
    print(f"idle {idle:.6f} s of {(hi - lo) / 1e9:.6f} s; "
          f"{100 * uncovered:.2f} % of it under no acai/ span")
    print(json.dumps({"gaps": named, "idle_s": idle,
                      "window_s": (hi - lo) / 1e9,
                      "uncovered_share": uncovered}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
