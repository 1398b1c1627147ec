"""Roofline analysis from compiled artifacts.

Three terms per (arch x shape x mesh), all in seconds:

    compute    = HLO_FLOPs_per_device / peak_FLOPs_chip
    memory     = HLO_bytes_per_device / HBM_bw
    collective = collective_bytes_per_device / ICI_link_bw

``cost_analysis()`` of the SPMD-partitioned module gives per-device FLOPs /
bytes. Collective bytes are NOT in cost_analysis: we parse the post-SPMD
HLO (``compiled.as_text()``) and sum the result-shape bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
(async -start forms included, -done skipped), with a size correction for
reduce-scatter (wire bytes ~ group_size x result bytes).

Hardware constants come from ``DEVICE_PEAKS``, keyed by the device kind
JAX reports; ``TARGET`` is the TPU v5e.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """One accelerator family's roofline constants.

    ``scale_dim`` names the resource dimension whose amount multiplies
    aggregate compute/bandwidth (e.g. ``"chips"`` on a TPU pod slice);
    ``ref_chips`` is the amount the registered cost models are normalized
    to (cost models give *total* work, so ``n = config[scale_dim] /
    ref_chips`` divides it across the slice). ``startup_s`` is the
    per-job provisioning + compile tax the roofline terms sit on top of.
    ``ici_bw`` is per link; 0 leaves out the interconnect term.
    """
    family: str
    peak_flops: float
    hbm_bw: float
    ici_bw: float = 0.0
    startup_s: float = 0.0
    scale_dim: Optional[str] = None
    ref_chips: float = 1.0

    def chips(self, config: dict) -> float:
        if self.scale_dim is None:
            return 1.0
        return max(float(config.get(self.scale_dim, self.ref_chips))
                   / self.ref_chips, 1e-9)


# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (4 links of 50 GB/s).
DEVICE_PEAKS: dict[str, HardwareSpec] = {
    "TPU v5 lite": HardwareSpec("TPU v5 lite", peak_flops=197e12,
                                hbm_bw=819e9, ici_bw=50e9,
                                scale_dim="chips", ref_chips=1.0),
}


def device_peaks(device_kind: str) -> HardwareSpec:
    """The peaks of one chip of ``device_kind``; an unknown kind raises."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to DEVICE_PEAKS with "
                       f"their source") from None


TARGET = DEVICE_PEAKS["TPU v5 lite"]      # the benchmark's chip

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# '%all-gather.5 = bf16[2,4096]{1,0} all-gather(' / tuple results
_INSTR_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9\[\],{}<=\s]+?)\s+"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|"
    r"collective-permute)\(", re.IGNORECASE)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_EXPLICIT_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_EXPLICIT_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


@dataclasses.dataclass
class CollectiveStats:
    bytes_by_kind: dict[str, int]
    count_by_kind: dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())


def parse_collectives(hlo_text: str) -> CollectiveStats:
    bytes_by: dict[str, int] = {k: 0 for k in _COLL_KINDS}
    count_by: dict[str, int] = {k: 0 for k in _COLL_KINDS}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2).lower()
        kind = op.replace("-start", "")
        b = _shape_bytes(shape_str)
        if kind == "reduce-scatter":
            b *= _group_size(line)       # result is the scattered shard
        # all-gather result already includes the gathered (full) size;
        # all-reduce result bytes ~ ring wire bytes per device (x2(n-1)/n ~ 2
        # ignored -> conservative)
        bytes_by[kind] += b
        count_by[kind] += 1
    return CollectiveStats(bytes_by, count_by)


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes: float
    collectives: CollectiveStats
    model_flops: float               # 6*N*D (train) / 2*N*tokens (serve)
    n_chips: int
    xla_cost_analysis: dict = dataclasses.field(default_factory=dict)

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / TARGET.peak_flops

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / TARGET.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / TARGET.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step-time estimate = max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / total HLO FLOPs — remat/redundancy waste catcher."""
        total = self.flops_per_device * self.n_chips
        return self.model_flops / total if total else float("nan")

    @property
    def roofline_fraction(self) -> float:
        """Fraction of chip peak spent on *useful* model FLOPs if the step
        ran at the roofline estimate: MODEL_FLOPS / (chips*peak*step_time)."""
        denom = self.n_chips * TARGET.peak_flops * self.step_time_s
        return self.model_flops / denom if denom else float("nan")

    def as_dict(self) -> dict[str, Any]:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes,
            "collective_breakdown": self.collectives.bytes_by_kind,
            "collective_counts": self.collectives.count_by_kind,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "step_time_s": self.step_time_s,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "n_chips": self.n_chips,
            "xla_cost_analysis_reference": self.xla_cost_analysis,
        }


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N_active*D (train) or 2*N_active*tokens (fwd-only)."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    # decode: one token per sequence + attention KV read flops
    flops = 2.0 * n * shape.global_batch
    if not cfg.attention_free:
        hd = cfg.resolved_head_dim
        n_attn_layers = sum(1 for k in cfg.layer_kinds()
                            if k in ("dense", "moe", "shared_attn"))
        flops += (4.0 * cfg.n_heads * hd * shape.seq_len
                  * shape.global_batch * n_attn_layers)
    return flops


def analyze(compiled, cfg, shape, n_chips: int,
            hlo_text: Optional[str] = None) -> Roofline:
    """Primary cost source is the HLO-text model (roofline/hlo_cost.py):
    XLA's cost_analysis() counts while-loop bodies once, which silently
    undercounts scan-over-layers models by ~n_layers (verified — see
    tests/test_roofline.py); the text model multiplies by
    known_trip_count. cost_analysis() is kept as a cross-check field."""
    from repro.roofline import hlo_cost
    text = hlo_text if hlo_text is not None else compiled.as_text()
    mc = hlo_cost.module_cost(text)
    xla_cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        xla_cost = {"flops": float(ca.get("flops", 0.0)),
                    "bytes": float(ca.get("bytes accessed", 0.0))}
    except Exception:  # noqa: BLE001
        pass
    colls = CollectiveStats(
        bytes_by_kind={k: int(v) for k, v in mc.coll_by_kind.items()},
        count_by_kind={k: int(v) for k, v in mc.coll_count.items()})
    r = Roofline(
        flops_per_device=mc.flops,
        bytes_per_device=mc.bytes_fused,
        collective_bytes=float(mc.coll_bytes),
        collectives=colls,
        model_flops=model_flops(cfg, shape),
        n_chips=n_chips,
    )
    r.xla_cost_analysis = xla_cost
    r.xla_cost_analysis["bytes_all_ops_upper_bound"] = mc.bytes
    return r
