"""Seconds of each checkpoint save spent committing it to the data lake:
upload session, hash, blob write, catalog and metadata (program span
``acai/lake/put``), per save."""
from bench import program_spans as P


def read(run):
    return P.mean_span(run, "lake/put")
