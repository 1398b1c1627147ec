"""Seconds of each checkpoint save spent copying the state's leaves from
the device to the host (program span ``acai/ckpt/fetch``), per save."""
from bench import program_spans as P


def read(run):
    return P.mean_span(run, "ckpt/fetch")
