"""chip_smoke.py on the CPU: it refuses to run without a TPU or outside a
checkout, and its serve, train and four-chip phases pass at reduced size
(the real sizes run only on the chip)."""
import dataclasses
import functools
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import chip_smoke
from repro.configs.base import get_arch

ROOT = Path(chip_smoke.__file__).resolve().parent


def _run(script: Path, cwd: Path, **env):
    return subprocess.run([sys.executable, str(script)], cwd=cwd,
                          capture_output=True, text=True, timeout=300,
                          env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_refuses_cpu():
    proc = _run(ROOT / "chip_smoke.py", ROOT)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU attached" in proc.stderr


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    proc = _run(tmp_path / "chip_smoke.py", tmp_path, PYTHONPATH="")
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_serve_phase_reduced():
    chip_smoke.check_serve(get_arch("olmo-1b").reduced(), 0, slots=4,
                           n_requests=6, prompt_len=8, max_new=4,
                           buffer_len=32)


def test_train_phase_reduced(tmp_path, monkeypatch):
    monkeypatch.setattr(chip_smoke, "TRAIN_BATCH", 4)
    monkeypatch.setattr(chip_smoke, "TRAIN_SEQ", 32)
    cut = dataclasses.replace(get_arch("olmo-1b").reduced(), n_layers=2)
    chip_smoke.check_train(cut, 0, tmp_path / "work")


FOUR_DEVICES = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, os.getcwd())
import chip_smoke
from repro.configs.base import get_arch
chip_smoke.get_arch = lambda name: get_arch(name).reduced()
chip_smoke.TRAIN_LAYERS, chip_smoke.TRAIN_BATCH, chip_smoke.TRAIN_SEQ = 2, 4, 32
chip_smoke.check_four_chips(0, steps=3, full_steps=2)
print("RESULT::" + __import__("json").dumps({"ok": True}))
"""


def test_four_chip_phase_on_virtual_devices():
    proc = subprocess.run([sys.executable, "-c", FOUR_DEVICES], cwd=ROOT,
                          capture_output=True, text=True, timeout=300,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT::")][0]
    assert json.loads(line[len("RESULT::"):]) == {"ok": True}
    assert "sharded vs one-device losses" in proc.stdout


CACHE_DIR = r"""
import jax
from repro.launch.compile_cache import use_compile_cache
print(use_compile_cache())
print(jax.config.jax_compilation_cache_dir)
"""


def test_compile_cache_follows_the_environment(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    run = functools.partial(subprocess.run, [sys.executable, "-c", CACHE_DIR],
                            cwd=ROOT, capture_output=True, text=True,
                            timeout=120)
    fixed = str(ROOT / ".jax_cache")
    proc = run(env={**env, "PYTHONPATH": str(ROOT / "src")})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [fixed, fixed]
    mine = str(tmp_path / "cache")
    proc = run(env={**env, "PYTHONPATH": str(ROOT / "src"),
                    "JAX_COMPILATION_CACHE_DIR": mine})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [mine, mine]
