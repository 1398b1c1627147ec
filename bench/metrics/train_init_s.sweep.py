"""Per trial: seconds in ``init_train`` (the step program built or loaded,
parameters and optimizer state created) and the dataset's registration
(program spans ``acai/train/init`` and ``acai/train/register``)."""
from bench import program_spans as P


def read(run):
    return P.per_count(run, ("train/init", "train/register"), "train/init")
