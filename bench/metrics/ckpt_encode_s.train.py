"""Seconds of each checkpoint save spent encoding the state as ``.npz``
in memory (program span ``acai/ckpt/encode``), per save."""
from bench import program_spans as P


def read(run):
    return P.mean_span(run, "ckpt/encode")
