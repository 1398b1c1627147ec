"""Roofline share (%) of the grouped expert products: the larger of their
FLOPs over peak and their bytes over bandwidth, for the work they do (the
forward, its recomputation and the backward's two products over the
``moe/gemm_rows`` rows the program counts, padding included; the held
experts' weights; ``flops_moe.expert_gemm_cost``), over the device time
of the operations under the ``moe_experts`` scope."""
from bench.readers_moe import expert_gemm_roofline as read  # noqa: F401
