"""Share (%) of the ``jit_train_step`` device time in operations under the
``moe_route``, ``moe_dispatch`` and ``moe_combine`` scopes: the router,
the sort and gather into expert order, and the weighted sum back."""
from bench.readers_moe import routing_share as read  # noqa: F401
