"""A whole run of each job kind on tiny cells, with the chip check skipped:
the window goes through the engine, nothing compiles inside it, and the
check passes the program and fails each fault planted under the timed
path and the float8 control in the program's place."""
import pytest

from bench import train_common as TC
from bench.jobs import generate_job


def _failed(result):
    return [n for n, c in result["compared"].items()
            if c["value"] > c["limit"]]


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.sweep",
                                  "tinyg.generate"])
def test_program_is_correct(run_tiny, cell):
    res = run_tiny(cell)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["compared"]["window_compiles"]["value"] == 0
    (e2e,) = [m for m in res["metrics"] if m != "setup_s"]
    assert res["metrics"][e2e]["value"] > 0
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("cell,fault", [
    ("tiny.train", "unchanged"),
    ("tiny.train", "half_batch"),
    ("tiny.sweep", "half_batch"),
    ("tinyg.generate", "altered_token"),
])
def test_fault_is_not_correct(run_tiny, cell, fault):
    res = run_tiny(cell, fault=fault)
    assert not res["correct"]
    failed = set(_failed(res)) - {"window_compiles"}
    assert failed, res["compared"]


def test_training_control_is_not_correct(run_tiny):
    seen = {}

    def after(run):
        seen["control"] = TC.control(run)
    run_tiny("tiny.train", after=after)
    failed = [n for n, v, lim in seen["control"] if v > lim]
    assert failed, seen["control"]


def test_generation_control_is_not_correct(run_tiny):
    seen = {}

    def after(run):
        seen["gap"] = generate_job.gaps(run, quant="fp8")
        seen["limit"] = run.cell.limits["logit_gap"]
    run_tiny("tinyg.generate", after=after)
    assert seen["gap"] > seen["limit"]
