"""§Perf hillclimb driver: named variants per chosen cell, re-lowered and
re-analyzed per iteration; JSON artifacts in benchmarks/results/perf/.

Run with 512 placeholder devices:
    PYTHONPATH=src python -m benchmarks.perf_hillclimb
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

import dataclasses
import json
from pathlib import Path

from repro.configs.base import get_arch, register
from repro.launch.dryrun import run_cell
from repro.launch.mesh import make_mesh
from repro.train.train_step import TrainConfig

OUT = Path("benchmarks/results/perf")


def measure(name, arch, shape, *, tcfg=None, mesh=None,
            serve_layout="fsdp"):
    r = run_cell(arch, shape, mesh=mesh, tcfg=tcfg, out_dir=None,
                 serve_layout=serve_layout, verbose=False)
    f = r["roofline"]
    row = {"variant": name, "arch": arch, "shape": shape,
           "chips": r["n_chips"],
           "compute_s": f["compute_s"], "memory_s": f["memory_s"],
           "collective_s": f["collective_s"], "dominant": f["dominant"],
           "step_time_s": f["step_time_s"],
           "useful": f["useful_flops_ratio"],
           "roofline_frac": f["roofline_fraction"],
           "coll_breakdown": f["collective_breakdown"],
           "serve_layout": serve_layout,
           "tcfg": dataclasses.asdict(tcfg) if tcfg else None}
    print(f"{name:34s} compute={f['compute_s']:7.3f} "
          f"memory={f['memory_s']:7.3f} coll={f['collective_s']:7.3f} "
          f"dom={f['dominant']:10s} roofline={f['roofline_fraction']:.3f}")
    OUT.mkdir(parents=True, exist_ok=True)
    (OUT / f"{name}.json").write_text(json.dumps(row, indent=1))
    return row


def cell_A():
    print("== Cell A: qwen3-32b x train_4k (paper-representative) ==")
    measure("A0_baseline", "qwen3-32b", "train_4k",
            tcfg=TrainConfig(remat="full"))
    measure("A1_remat_dots", "qwen3-32b", "train_4k",
            tcfg=TrainConfig(remat="dots"))
    measure("A2_dots_bf16stream", "qwen3-32b", "train_4k",
            tcfg=TrainConfig(remat="dots", param_stream_dtype="bfloat16"))
    mesh328 = make_mesh((32, 8), ("data", "model"))
    measure("A3_dots_bf16_mesh32x8", "qwen3-32b", "train_4k",
            tcfg=TrainConfig(remat="dots", param_stream_dtype="bfloat16"),
            mesh=mesh328)
    mesh644 = make_mesh((64, 4), ("data", "model"))
    measure("A4_dots_bf16_mesh64x4", "qwen3-32b", "train_4k",
            tcfg=TrainConfig(remat="dots", param_stream_dtype="bfloat16"),
            mesh=mesh644)
    mesh1282 = make_mesh((128, 2), ("data", "model"))
    measure("A5_dots_bf16_mesh128x2", "qwen3-32b", "train_4k",
            tcfg=TrainConfig(remat="dots", param_stream_dtype="bfloat16"),
            mesh=mesh1282)
    measure("A6_dots_bf16_mesh256x1", "qwen3-32b", "train_4k",
            tcfg=TrainConfig(remat="dots", param_stream_dtype="bfloat16"),
            mesh=make_mesh((256, 1), ("data", "model")))
    measure("A7_master_bf16_mesh128x2", "qwen3-32b", "train_4k",
            tcfg=TrainConfig(remat="dots", master_weights=True),
            mesh=mesh1282)


def cell_B():
    print("== Cell B: llama4-scout x train_4k (most collective-bound) ==")
    measure("B0_baseline", "llama4-scout-17b-a16e", "train_4k",
            tcfg=TrainConfig(remat="full"))
    measure("B1_dots_bf16stream", "llama4-scout-17b-a16e", "train_4k",
            tcfg=TrainConfig(remat="dots", param_stream_dtype="bfloat16"))
    base = get_arch("llama4-scout-17b-a16e")
    fused = dataclasses.replace(
        base, name="llama4-scout-fused",
        moe=dataclasses.replace(base.moe, fuse_shared=True))
    register(fused)
    measure("B2_fused_shared", "llama4-scout-fused", "train_4k",
            tcfg=TrainConfig(remat="dots", param_stream_dtype="bfloat16"))
    mesh328 = make_mesh((32, 8), ("data", "model"))
    measure("B3_fused_mesh32x8", "llama4-scout-fused", "train_4k",
            tcfg=TrainConfig(remat="dots", param_stream_dtype="bfloat16"),
            mesh=mesh328)
    measure("B4_fused_mesh64x4", "llama4-scout-fused", "train_4k",
            tcfg=TrainConfig(remat="dots", param_stream_dtype="bfloat16"),
            mesh=make_mesh((64, 4), ("data", "model")))
    measure("B6_master_mesh32x8", "llama4-scout-fused", "train_4k",
            tcfg=TrainConfig(remat="dots", master_weights=True),
            mesh=mesh328)


def cell_C():
    print("== Cell C: qwen3-32b x decode_32k (serving latency) ==")
    measure("C0_baseline_fsdp", "qwen3-32b", "decode_32k")
    measure("C2_resident_tp_only", "qwen3-32b", "decode_32k",
            serve_layout="resident")


if __name__ == "__main__":
    import sys
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which in ("all", "A"):
        cell_A()
    if which in ("all", "B"):
        cell_B()
    if which in ("all", "C"):
        cell_C()
