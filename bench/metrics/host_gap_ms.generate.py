"""Device idle between consecutive ``jit_serve_step`` executions, per
step, in ms (profiler trace)."""
from bench.readers import decode_host_gap_ms as read  # noqa: F401
