"""Model FLOPs of the train steps (forward and backward, remat not
counted; ``flops.train_flops_per_token``) over the device time of the
``jit_train_step`` executions x chips x peak, in %."""
from bench.readers import train_mfu as read  # noqa: F401
