"""What one cell is: its entry in ``BENCHMARK.json`` and the files named by it.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by name:

  configs/<config>.json     sizes as run (``reduced`` keys cut from source)
  traffic/<traffic>.json    the mix; its ``job`` names jobs/<job>.py
  pace/<cell>.json          how much work one second of window holds
  limits/<cell>.json        the limit of each number that decides correct
  metrics/<metric>.py       a reader with ``read(run) -> float | None``

so a later cell or metric is added as files, without an edit here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    pace: dict
    limits: dict
    chips: int
    end_to_end: tuple          # metric entries this cell reports
    per_layer: tuple


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> dict:
    return _read_json(root / "BENCHMARK.json")


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_names


def load_cell(name: str, root: Path = ROOT, bench: Path = BENCH) -> Cell:
    """The cell ``name`` of ``root``'s BENCHMARK.json, with its files."""
    spec = load_benchmark(root)
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in spec["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    cfg_entry = next(c for c in spec["configs"]
                     if c["name"] == entry["config"])
    e2e = tuple(m for m in spec["end_to_end"]
                if "workloads" not in m or name in m["workloads"])
    e2e_names = {m["name"] for m in e2e}
    per_layer = tuple(m for m in spec["per_layer"]
                      if _reports(m, name, e2e_names))
    return Cell(
        name=name,
        config=_read_json(root / cfg_entry["file"]),
        traffic=_read_json(bench / "traffic" / f"{entry['traffic']}.json"),
        pace=_read_json(bench / "pace" / f"{name}.json"),
        limits=_read_json(bench / "limits" / f"{name}.json"),
        chips=int(entry["chips"]),
        end_to_end=e2e,
        per_layer=per_layer,
    )


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    mod_name = "bench_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def job_module(cell: Cell, bench: Path = BENCH):
    return load_module(bench / "jobs" / f"{cell.traffic['job']}.py")


def metric_reader(name: str, bench: Path = BENCH):
    return load_module(bench / "metrics" / f"{name}.py").read


def device_peaks(kind: str, bench: Path = BENCH) -> dict:
    """Published peaks of one chip of ``kind``; an unknown kind raises."""
    table = _read_json(bench / "peaks.json")["kinds"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table[kind]


def arch_fields(config: dict) -> dict:
    """The keys of a configuration file that the program's ArchConfig
    takes; the rest (published sizes, deployment, assumptions) is prose."""
    keep = ("name", "family", "n_layers", "d_model", "n_heads",
            "n_kv_heads", "head_dim", "d_ff", "vocab_size",
            "parametric_norm", "norm_type", "norm_eps", "rope_theta",
            "tie_embeddings")
    return {k: config[k] for k in keep if k in config}
