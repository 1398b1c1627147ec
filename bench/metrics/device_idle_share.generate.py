"""1 - (union of device operation intervals / traced window), in %,
averaged over the cell's chips (profiler trace)."""
from bench.readers import idle_share as read  # noqa: F401
