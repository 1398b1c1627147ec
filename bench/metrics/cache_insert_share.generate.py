"""Share (%) of the ``jit_serve_step`` device time spent in operations
whose op-name path holds ``cache_insert`` (the KV cache insert's
``jax.named_scope``; the ``tf_op`` stat of the TPU trace)."""
from bench import program_spans as P


def read(run):
    return P.scoped_share(run, "serve_step", "cache_insert")
