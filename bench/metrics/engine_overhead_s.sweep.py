"""Per trial: host clock from ``AcaiEngine.submit`` to FINISHED, less the
time inside the job function."""
from bench.readers import engine_overhead as read  # noqa: F401
