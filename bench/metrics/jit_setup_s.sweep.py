"""Per trial: JAX's own tracing, lowering and compile-or-cache-load
seconds (``jax.monitoring`` duration events)."""
from bench.readers import jit_setup as read  # noqa: F401
