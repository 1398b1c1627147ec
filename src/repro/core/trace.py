"""Program spans on the profiler's clock.

``span(name)`` marks a region of host code as ``acai/<name>`` in the JAX
profiler's trace, on the same clock as the device's operations. It keeps
nothing in memory and has no switch: when no profiler session records, an
annotation costs about a microsecond. Collect spans with
``jax.profiler.start_trace`` (or ``start_server`` and a remote capture) and
read them in xprof or Perfetto; ``docs/engine.md`` lists the names.

Names are fixed strings: one span per phase, never one per slot or leaf.
Code that runs before anything imported ``jax`` (the engine's control
plane may) gets a null context, so importing this module never pulls
``jax`` in.
"""
from __future__ import annotations

import contextlib
import sys

PREFIX = "acai/"


def span(name: str):
    """Context manager recording ``acai/<name>`` while a profiler traces."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(PREFIX + name)
