"""BENCHMARK.json and the files it names: every cell finds its
configuration, traffic, pace, limits and job kind, every per-layer metric
its reader; a cell added as new files loads with nothing else edited; the
peaks table refuses an unknown chip; the command refuses a machine with
no TPU and prints no result."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import spec as S

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return S.load_benchmark()


def test_every_cell_loads_its_files(bench):
    configs = {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        assert w["config"] in configs
        cell = S.load_cell(w["name"])
        assert (S.BENCH / "jobs" / f"{cell.traffic['job']}.py").is_file()
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert "window_compiles" in cell.limits
    for c in bench["configs"]:
        assert (S.ROOT / c["file"]).is_file()
        cfg = json.loads((S.ROOT / c["file"]).read_text())
        assert cfg["published"].keys() <= set(c["reduced"])


def test_every_metric_has_a_reader_and_moves_a_reported_metric(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert callable(S.metric_reader(m["name"]))
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", [cell])


def test_names_and_units_keep_to_the_alphabet(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])


def test_entries_hold_just_their_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert not any(k.endswith(("_dim", "_rank")) or k in
                       ("d_model", "d_ff", "n_heads", "n_kv_heads")
                       for k in c["reduced"])
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


def test_a_new_cell_loads_from_new_files_alone(tmp_path):
    root = tmp_path
    shutil.copy(S.ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(S.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((S.BENCH / "configs" / "olmo-1b.json").read_text())
    cfg.update(name="olmo-1b-long", n_layers=2)
    (root / "bench" / "configs" / "olmo-1b-long.json").write_text(
        json.dumps(cfg))
    traffic = json.loads(
        (S.BENCH / "traffic" / "train_b8_s1024.json").read_text())
    traffic.update(global_batch=4, seq_len=2048)
    (root / "bench" / "traffic" / "train_b4_s2048.json").write_text(
        json.dumps(traffic))
    (root / "bench" / "pace" / "olmo-1b-long.train.json").write_text(
        '{"steps_per_s": 1.0}')
    shutil.copy(S.BENCH / "limits" / "olmo-1b.train.json",
                root / "bench" / "limits" / "olmo-1b-long.train.json")
    spec["configs"].append({"name": "olmo-1b-long", "source": "x",
                            "file": "bench/configs/olmo-1b-long.json",
                            "reduced": ["n_layers"], "why": "x"})
    spec["workloads"].append({"name": "olmo-1b-long.train",
                              "config": "olmo-1b-long",
                              "traffic": "train_b4_s2048", "chips": 1,
                              "why": "x"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "olmo-1b.train" in m.get("workloads", []):
            m["workloads"].append("olmo-1b-long.train")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    for name in ("bench/jobs/train_job.py", "bench/harness.py",
                 "bench/spec.py"):
        assert (root / name).read_bytes() == (S.ROOT / name).read_bytes()
    cell = S.load_cell("olmo-1b-long.train", root, root / "bench")
    assert cell.traffic["seq_len"] == 2048 and cell.config["n_layers"] == 2
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s",
                                                    "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        m["name"] for m in S.load_cell("olmo-1b.train").per_layer}


def test_peaks_refuse_an_unknown_chip():
    assert S.device_peaks("TPU v5 lite")["peak_flops"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        S.device_peaks("TPU v99")


def _command(cwd, **env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "olmo-1b.train",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_command_refuses_cpu():
    proc = _command(S.ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU attached" in proc.stderr


def test_command_refuses_a_tree_of_the_benchmark_alone(tmp_path, bench):
    shutil.copy(S.ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(S.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(tmp_path, PYTHONPATH="")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
