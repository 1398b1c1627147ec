"""Seconds the caller is blocked in ``CheckpointManager.save``, per save in
the window (host span around the program's method)."""
from bench.readers import mean_span


def read(run):
    return mean_span(run, "ckpt_save")
