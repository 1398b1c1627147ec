"""The decode step's roofline share, in %: the least time of each step
(larger of FLOPs over peak and required bytes over bandwidth;
``flops.decode_step_cost``) over the device time of the
``jit_serve_step`` executions."""
from bench.readers import decode_roofline as read  # noqa: F401
