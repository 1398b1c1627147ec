"""The program's spans (``repro.core.trace``): a reduced training job
submitted through the engine, with its checkpoint save, and a serve loop,
run under a CPU profiler trace. Every span appears, nested as
``docs/engine.md`` lists them, and the step metrics reach the job's
metadata through the log parser."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs.base import get_arch
from repro.core.acai import AcaiEngine, AcaiProject
from repro.core.engine.lifecycle import JobState
from repro.core.engine.registry import JobSpec
from repro.launch.serve import serve
from repro.launch.train import train
from repro.models import model as M

SRC = Path(__file__).resolve().parents[1] / "src"
STEPS = 4

# each span -> the span it sits in (None: outermost of its call)
NESTED_IN = {
    "engine/launch": None,
    "engine/materialize": "engine/launch",
    "engine/job_fn": "engine/launch",
    "engine/upload": "engine/launch",
    "engine/finalize": "engine/launch",
    "train/init": "engine/job_fn",
    "train/register": "engine/job_fn",
    "train/steps": "engine/job_fn",
    "train/dispatch": "train/steps",
    "train/wait": "train/steps",
    "ckpt/save": "train/steps",
    "ckpt/fetch": "ckpt/save",
    "ckpt/encode": "ckpt/save",
    "lake/put": "ckpt/save",
    "lake/hash": "lake/put",
    "lake/write": "lake/put",
    "serve/init": None,
    "serve/dispatch": None,
    "serve/sync": None,
    "serve/host": None,
}


def _spans(trace_dir):
    """[(name, start_ns, end_ns)] of the ``acai/`` host spans recorded."""
    from jax.profiler import ProfileData

    (path,) = Path(trace_dir).rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("acai/"):
                    out.append((ev.name[len("acai/"):], ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def _innermost_parent(span, spans):
    name, s, e = span
    outer = [p for p in spans if p is not span and p[1] <= s and e <= p[2]
             and p[2] - p[1] >= e - s]
    return min(outer, key=lambda p: p[2] - p[1])[0] if outer else None


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Spans, the job's metadata and the serve result of one traced run."""
    root = tmp_path_factory.mktemp("spans")
    cfg = get_arch("olmo-1b").reduced()
    project = AcaiProject("p", root / "lake")
    project.upload("/data/in.txt", b"42", creator="u")
    project.create_file_set("inputs", ["/data/in.txt"], creator="u")
    engine = AcaiEngine(datalake=project, workroot=str(root / "jobs"),
                        runner="local")

    def fn(workdir, job):
        res = train(cfg, project, "traced", steps=STEPS, seq_len=16,
                    global_batch=4, data_vocab=64, save_every=STEPS)
        return {"steps": res.report.steps_run}

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(root / "trace"), profiler_options=opts)
    try:
        handle = engine.submit(JobSpec(name="train", project="p", user="u",
                                       fn=fn, input_fileset="inputs",
                                       output_fileset="outputs"))
        state = handle.wait()
        served = serve(cfg, params, prompts, slots=2, buffer_len=16,
                       max_new=3)
    finally:
        jax.profiler.stop_trace()
    assert state == JobState.FINISHED, handle.job.error
    return {"spans": _spans(root / "trace"),
            "meta": project.metadata.get(handle.job.job_id),
            "ticks": served.ticks}


def test_every_span_is_recorded_where_it_belongs(traced):
    spans = traced["spans"]
    names = {s[0] for s in spans}
    assert set(NESTED_IN) <= names
    parents: dict[str, set] = {}
    for s in spans:
        parents.setdefault(s[0], set()).add(_innermost_parent(s, spans))
    for name, parent in NESTED_IN.items():
        assert parent in parents[name], (name, parents[name])
    # spans that run in one place only are nowhere else
    for name in ("engine/job_fn", "train/init", "train/steps",
                 "train/dispatch", "train/wait", "ckpt/save", "ckpt/fetch",
                 "ckpt/encode", "lake/put", "serve/dispatch", "serve/sync",
                 "serve/host"):
        assert parents[name] == {NESTED_IN[name]}, (name, parents[name])


def test_one_span_per_step_tick_and_save(traced):
    count = {}
    for name, _, _ in traced["spans"]:
        count[name] = count.get(name, 0) + 1
    assert count["train/dispatch"] == STEPS
    assert count["ckpt/save"] == count["ckpt/fetch"] == 1
    assert count["lake/put"] == 1
    for name in ("serve/dispatch", "serve/sync", "serve/host"):
        assert count[name] == traced["ticks"]


def test_step_metrics_reach_the_job_metadata(traced):
    meta = traced["meta"]
    assert meta["step_s_median"] > 0
    assert meta["straggler_steps"] in range(STEPS)
    assert meta["ckpt_save_s"] > 0


def test_the_engine_imports_and_spans_without_jax():
    code = ("import sys\n"
            "import repro.core.engine.launcher\n"
            "from repro.core.trace import span\n"
            "with span('engine/launch'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
    env = dict(os.environ, PYTHONPATH=str(SRC))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)

