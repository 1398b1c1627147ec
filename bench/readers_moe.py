"""Readers of the MoE training cell's per-layer metrics
(``metrics/<name>.moonlight.py``): the step's model FLOPs utilization, the
grouped expert products' roofline share and the routing's share of the
step, from the profiler trace, the program's op-name scopes and its
routing counters (``moe/...``, kept per job by the train loop).

Each returns None where the run holds nothing to read: no trace, no
scope on the trace's operations, or no counters from the program.
"""
from __future__ import annotations

from bench import flops_moe as F
from bench import program_spans as P
from bench import readers as R
from bench import trace_reduce as T

ROUTING_SCOPES = ("moe_route", "moe_dispatch", "moe_combine")


def _counters(run):
    return (run.state.get("sink") or {}).get("counters") or {}


def train_mfu(run):
    """Model FLOPs of the window's train steps over their device time x
    chips x peak, in %."""
    per_plane = R._runs(run, "train_step")
    held = _counters(run).get("moe/held_rows")
    if per_plane is None or held is None:
        return None
    t = run.cell.traffic
    tokens = t["global_batch"] * t["seq_len"]
    flops = F.train_step_flops(run.cell.config, tokens, t["seq_len"],
                               held) * len(per_plane[0])
    busy = R.step_device_s(per_plane) * run.cell.chips * \
        run.peaks["peak_flops"]
    return 100.0 * flops / busy


def scoped_seconds(run, function: str, scope: str):
    """Device seconds, averaged over the chips, of ``jit_<function>``'s
    operations whose op-name path holds ``scope``; None where no operation
    does."""
    s = run.trace_summary
    if s is None:
        return None
    paths = P.of(run)["op_paths"]
    total = []
    for plane in s["planes"]:
        runs = T.union((a, b) for a, b in T.module_runs(
            run.trace_events, plane, function) if a >= s["lo"]
            and b <= s["hi"])
        named = paths.get(plane, {})
        starts = [a for a, _ in runs]
        total.append(sum(
            P._overlap(e["start_ns"], e["start_ns"] + e["dur_ns"], runs,
                       starts)
            for e in T.leaf_ops(run.trace_events, plane)
            if scope in named.get(e["name"], "")))
    if not any(total):
        return None
    return sum(total) / len(total) / 1e9


def expert_gemm_roofline(run):
    """Least time of the window's grouped expert products (the larger of
    FLOPs over peak and bytes over bandwidth, ``flops_moe``) over the
    device time of the operations under ``moe_experts``, in %."""
    per_plane = R._runs(run, "train_step")
    rows = _counters(run).get("moe/gemm_rows")
    busy = scoped_seconds(run, "train_step", "moe_experts")
    if per_plane is None or rows is None or not busy:
        return None
    flops, nbytes = F.expert_gemm_cost(run.cell.config, rows)
    least = max(flops / run.peaks["peak_flops"],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least * len(per_plane[0]) / busy


def routing_share(run):
    """Share (%) of ``jit_train_step`` device time in operations under the
    routing scopes: the router, the sort and gather into expert order,
    and the weighted sum back."""
    per_plane = R._runs(run, "train_step")
    if per_plane is None:
        return None
    parts = [scoped_seconds(run, "train_step", sc) for sc in ROUTING_SCOPES]
    if all(p is None for p in parts):
        return None
    return 100.0 * sum(p or 0.0 for p in parts) / R.step_device_s(per_plane)
