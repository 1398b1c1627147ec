"""Model FLOPs of the train steps (MLA, the routed pairs computed here as
the program counts them in ``moe/held_rows``, the shared experts, the
dense layer and the head; forward and backward, remat not counted;
``flops_moe.train_step_flops``) over the device time of the
``jit_train_step`` executions x chips x peak, in %."""
from bench.readers_moe import train_mfu as read  # noqa: F401
