"""The reduction from trace events to busy time, idle gaps and step
executions: on a hand-made trace with known answers, and on a small trace
recorded on a TPU v5e and trimmed (``bench/fixtures``)."""
import gzip
import json

import pytest

from bench import trace_reduce as T
from bench.spec import BENCH

DEV = "/device:TPU:0"


def _ev(line, name, start, dur, plane=DEV):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


HAND = [
    _ev("", "bench/window", 0, 1000, plane="/host:CPU"),
    _ev("", "bench/ckpt_save", 600, 300, plane="/host:CPU"),
    _ev(T.MODULES_LINE, "jit_train_step(7)", 100, 200),
    _ev(T.MODULES_LINE, "jit_train_step(7)", 350, 100),
    _ev(T.MODULES_LINE, "jit_train_step_other(3)", 950, 10),
    _ev(T.OPS_LINE, "%while.4 = (s32[]) while(%t)", 100, 200),
    _ev(T.OPS_LINE, "%fusion.1 = bf16[8] fusion(%a)", 100, 150),
    _ev(T.OPS_LINE, "%fusion.2 = bf16[8] fusion(%b)", 250, 50),
    _ev(T.OPS_LINE, "%fusion.1 = bf16[8] fusion(%a)", 350, 100),
    _ev(T.OPS_LINE, "%copy.3 = bf16[8] copy(%c)", 950, 100),   # past the end
]


def test_hand_made_trace():
    lo, hi = T.window(HAND)
    assert (lo, hi) == (0.0, 1000.0)
    assert T.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert T.busy_ns(HAND, DEV, lo, hi) == 200 + 100 + 50
    assert [T.op_name(e["name"]) for e in T.leaf_ops(HAND, DEV)] == [
        "fusion.1", "fusion.2", "fusion.1", "copy.3"]
    assert T.idle_gaps(HAND, DEV, lo, hi) == [(0, 100), (300, 350),
                                             (450, 950)]
    assert T.module_runs(HAND, DEV, "train_step") == [(100, 300),
                                                      (350, 450)]
    s = T.summary(HAND)
    assert s["window_s"] == 1e-6 and s["busy_s"] == 350e-9
    assert s["breakdown"]["device_ops"][0] == ["fusion.1", 250e-9]
    gaps = s["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench/ckpt_save", 500e-9]
    assert gaps[1] == ["bench/window", 100e-9]


def test_a_trace_without_a_device_is_refused():
    with pytest.raises(ValueError, match="no TPU device plane"):
        T.summary(HAND[:2])


def test_load_events_reads_the_benchmark_spans_of_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench/window"):
        with jax.profiler.TraceAnnotation("bench/job"):
            jax.jit(lambda x: x @ x)(jnp.ones((64, 64))).block_until_ready()
        with jax.profiler.TraceAnnotation("not-ours"):
            pass
    jax.profiler.stop_trace()
    events = T.load_events(str(tmp_path))
    assert sorted(e["name"] for e in events) == ["bench/job", "bench/window"]
    lo, hi = T.window(events)
    (job,) = T.host_spans(events, "bench/job")
    assert lo <= job["start_ns"] and job["start_ns"] + job["dur_ns"] <= hi
    # the CPU backend has no TPU plane: no device metric comes from it
    with pytest.raises(ValueError, match="no TPU device plane"):
        T.summary(events)
