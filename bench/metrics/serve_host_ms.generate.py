"""Per decode tick: host milliseconds in the step call and the slot loop
(program spans ``acai/serve/dispatch`` and ``acai/serve/host``): the
serve loop's host work, without its wait for the step's tokens."""
from bench import program_spans as P


def read(run):
    return P.per_count(run, ("serve/dispatch", "serve/host"),
                       "serve/dispatch", scale=1e3)
