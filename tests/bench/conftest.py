"""Tiny cells of the benchmark for the CPU: the same harness, job kinds,
reference and checks as the chip cells, at sizes a test run holds."""
import json
import time

import pytest

TINY = {
    "configs": {
        "tiny": {"name": "tiny", "family": "dense", "n_layers": 2,
                 "d_model": 64, "n_heads": 4, "n_kv_heads": 4, "d_ff": 128,
                 "vocab_size": 256, "parametric_norm": False,
                 "norm_type": "layernorm", "norm_eps": 1e-5,
                 "rope_theta": 10000.0, "tie_embeddings": True},
        "tinyg": {"name": "tinyg", "family": "dense", "n_layers": 2,
                  "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                  "head_dim": 16, "d_ff": 128, "vocab_size": 256,
                  "parametric_norm": True, "norm_type": "rmsnorm",
                  "norm_eps": 1e-5, "rope_theta": 1e6,
                  "tie_embeddings": False}},
    "traffic": {
        "t": {"job": "train_job", "global_batch": 4, "seq_len": 32,
              "data_vocab": 64, "markov_temp": 2.5, "lr": 0.003,
              "warmup_steps": 5, "remat": "full"},
        "s": {"job": "sweep", "global_batch": 4, "seq_len": 32,
              "data_vocab": 64, "markov_temp": 2.5, "lrs": [0.001, 0.003],
              "warmup_steps": 5, "trial_steps": 6, "remat": "full"},
        "g": {"job": "generate_job", "slots": 4, "buffer_len": 64,
              "prompt_median": 8, "prompt_sigma": 0.8, "prompt_min": 2,
              "prompt_max": 40, "max_new": 8, "check_requests": 3}},
    "workloads": {"tiny.train": ("tiny", "t", {"steps_per_s": 2.0}),
                  "tiny.sweep": ("tiny", "s", {"trial_s": 2.0}),
                  "tinyg.generate": ("tinyg", "g", {"requests_per_s": 2.0})},
}
# Limits at these sizes, set as the chip cells' are: between what the
# program reads (bfloat16 compute) and what the float8 control reads.
TINY_LIMITS = {
    "train": {"data_mismatch": 0, "loss_gap": 1e-3, "grad_norm_gap": 1e-2,
              "param_change_gap": 0.02, "window_compiles": 0},
    "generate": {"logit_gap": 0.05, "window_compiles": 0},
}


def write_tiny(root):
    """A checkout-like tree holding the tiny cells' files."""
    b = root / "bench"
    for sub in ("configs", "traffic", "pace", "limits"):
        (b / sub).mkdir(parents=True, exist_ok=True)
    for name, cfg in TINY["configs"].items():
        (b / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, t in TINY["traffic"].items():
        (b / "traffic" / f"{name}.json").write_text(json.dumps(t))
    spec = {"configs": [{"name": n, "file": f"bench/configs/{n}.json"}
                        for n in TINY["configs"]],
            "workloads": [], "per_layer": [],
            "end_to_end": [
                {"name": "train_tokens_per_s", "unit": "tokens/s",
                 "workloads": ["tiny.train"]},
                {"name": "sweep_tokens_per_s", "unit": "tokens/s",
                 "workloads": ["tiny.sweep"]},
                {"name": "gen_tokens_per_s", "unit": "tokens/s",
                 "workloads": ["tinyg.generate"]},
                {"name": "setup_s", "unit": "s"}]}
    for cell, (cfg, traffic, pace) in TINY["workloads"].items():
        spec["workloads"].append({"name": cell, "config": cfg,
                                  "traffic": traffic, "chips": 1})
        (b / "pace" / f"{cell}.json").write_text(json.dumps(pace))
        kind = "generate" if cell.endswith("generate") else "train"
        (b / "limits" / f"{cell}.json").write_text(
            json.dumps(TINY_LIMITS[kind]))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return write_tiny(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(scope="module")
def run_tiny(tiny_root):
    """run_tiny(cell, fault=None, after=None) -> the run's result."""
    from bench import harness, spec

    def go(name, fault=None, after=None, seed=2**31 + 7):
        cell = spec.load_cell(name, tiny_root, tiny_root / "bench")
        return harness.execute(cell, seed, 4, False,
                               t_start=time.perf_counter(), root=tiny_root,
                               require_tpu=False, fault=fault, after=after)
    return go
