"""Collate dry-run JSON artifacts into the EXPERIMENTS.md §Roofline table
(+ per-cell bottleneck advice)."""
from __future__ import annotations

import json
from pathlib import Path

RESULTS = Path(__file__).parent / "results" / "dryrun"


def advice(r: dict) -> str:
    """One sentence on what would move the dominant term down (per cell)."""
    f = r["roofline"]
    dom = f["dominant"]
    kind = ("train" if "train" in r["shape"] else
            "decode" if "decode" in r["shape"] or "long" in r["shape"]
            else "prefill")
    coll = f.get("collective_breakdown", {})
    ag = coll.get("all-gather", 0)
    ar = coll.get("all-reduce", 0)
    if dom == "collective":
        if kind == "decode":
            return ("switch to the resident serving layout (bf16 TP-only "
                    "weights, no per-token FSDP gathers) — §Perf C")
        if ar >= ag:
            return ("reduce TP width toward data-parallel (TP psum bytes "
                    "scale with local tokens) — §Perf A3-A5/B3")
        return ("raise TP width or stream bf16 params (FSDP gather-bound) "
                "— §Perf A6 shows the opposite wall")
    if dom == "memory":
        if kind == "decode":
            return ("a flash-decode kernel keeps the score traffic in VMEM "
                    "(ROADMAP A3)")
        if f.get("useful_flops_ratio", 1) < 0.6:
            return ("dots-saveable remat + Pallas flash attention cut "
                    "recompute and score HBM traffic — §Perf A1/A8")
        return ("Pallas fused kernels (attention/WKV6/SSD) keep block "
                "intermediates in VMEM — kernels/ lower on real TPU")
    return ("compute-bound: raise useful ratio (lighter remat, causal "
            "block-skip in the Pallas kernel), or add chips")


def load(results_dir=RESULTS) -> list[dict]:
    rows = []
    for p in sorted(Path(results_dir).glob("*.json")):
        rows.append(json.loads(p.read_text()))
    return rows


def markdown_table(rows: list[dict], *, multi_pod: bool = False) -> str:
    hdr = ("| arch | shape | chips | compute_s | memory_s | collective_s | "
           "dominant | MODEL_FLOPS | useful | roofline_frac |\n"
           "|---|---|---|---|---|---|---|---|---|---|\n")
    out = [hdr]
    for r in rows:
        if r.get("multi_pod") != multi_pod:
            continue
        if r.get("status") == "n/a":
            out.append(f"| {r['arch']} | {r['shape']} | - | - | - | - | "
                       f"N/A | - | - | - |\n")
            continue
        f = r["roofline"]
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['n_chips']} "
            f"| {f['compute_s']:.3f} | {f['memory_s']:.3f} "
            f"| {f['collective_s']:.3f} | {f['dominant']} "
            f"| {f['model_flops']:.3e} | {f['useful_flops_ratio']:.2f} "
            f"| {f['roofline_fraction']:.3f} |\n")
    return "".join(out)


def summary(rows: list[dict]) -> dict:
    ok = [r for r in rows if r.get("status") == "ok"]
    single = [r for r in ok if not r["multi_pod"]]
    multi = [r for r in ok if r["multi_pod"]]
    na = [r for r in rows if r.get("status") == "n/a"]
    return {
        "cells_ok_single": len(single), "cells_ok_multi": len(multi),
        "cells_na": len(na),
        "worst_roofline": min(
            ((r["arch"], r["shape"], r["roofline"]["roofline_fraction"])
             for r in single), key=lambda t: t[2], default=None),
        "best_roofline": max(
            ((r["arch"], r["shape"], r["roofline"]["roofline_fraction"])
             for r in single), key=lambda t: t[2], default=None),
    }


def advice_table(rows: list[dict], *, multi_pod: bool = False) -> str:
    out = ["| arch | shape | dominant | what moves it down |\n"
           "|---|---|---|---|\n"]
    for r in rows:
        if r.get("multi_pod") != multi_pod or r.get("status") != "ok":
            continue
        out.append(f"| {r['arch']} | {r['shape']} | "
                   f"{r['roofline']['dominant']} | {advice(r)} |\n")
    return "".join(out)


def annotate(results_dir=RESULTS) -> None:
    """Write the advice sentence back into each JSON artifact."""
    for p in sorted(Path(results_dir).glob("*.json")):
        r = json.loads(p.read_text())
        if r.get("status") == "ok":
            r["bottleneck_advice"] = advice(r)
            p.write_text(json.dumps(r, indent=1))


if __name__ == "__main__":
    rows = load()
    print(markdown_table(rows))
    print(advice_table(rows))
    print(json.dumps(summary(rows), indent=1))
    annotate()
