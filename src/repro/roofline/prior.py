"""Roofline cold-start priors: analytical runtime estimates for placement.

The profiler's log-linear models need measured runs to exist; a cold
cluster has none, and placement used to default every unknown template to
``duration or 1.0`` — silently collapsing the cost/speed frontier the
auto-provisioner is supposed to find. This module derives a *prior*
runtime estimate from the same roofline arithmetic as
``roofline/analysis.py``: a template registers an analytic cost
(FLOPs / HBM bytes / collective bytes as functions of the job config —
or fixed numbers parsed out of an HLO module via ``hlo_cost``), each
accelerator family registers its hardware constants, and the estimate is

    t = startup + max(flops / (peak * n), bytes / (hbm_bw * n),
                      coll_bytes / ici_bw)

with ``n`` the config's chip count on families whose compute scales with
a resource dimension. ``Profiler(prior=...)`` serves these from
``predict_for_pool`` whenever no fitted model exists, and online
``add_observation`` feedback replaces the prior with a measured per-pool
model as soon as real runtimes arrive (see docs/engine.md, "Profiler
feedback loop").
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Union

from repro.roofline.analysis import HardwareSpec  # noqa: F401 (re-export)

CostFn = Union[float, Callable[[dict], float]]


def roofline_ceiling_s(flops: float, nbytes: float,
                       hw: HardwareSpec, coll_bytes: float = 0.0,
                       n_chips: float = 1.0) -> float:
    """Best-case seconds for a workload on ``hw``: the roofline max of
    the compute / memory / interconnect terms (no startup)."""
    n = max(n_chips, 1e-9)
    return max(flops / (hw.peak_flops * n),
               nbytes / (hw.hbm_bw * n),
               coll_bytes / hw.ici_bw if hw.ici_bw else 0.0)


@dataclasses.dataclass
class TemplateCost:
    """Analytic cost of one command template as functions of the job
    config (numeric args + resource shape — the same dict placement
    feeds ``predict_for_pool``). Constants are accepted where the cost
    does not depend on the config."""
    flops: CostFn = 0.0
    nbytes: CostFn = 0.0
    coll_bytes: CostFn = 0.0

    @staticmethod
    def _eval(fn: CostFn, config: dict) -> float:
        return float(fn(config)) if callable(fn) else float(fn)

    def evaluate(self, config: dict) -> tuple[float, float, float]:
        return (self._eval(self.flops, config),
                self._eval(self.nbytes, config),
                self._eval(self.coll_bytes, config))

    @classmethod
    def from_hlo(cls, hlo_text: str, *,
                 scale_by: Optional[str] = None) -> "TemplateCost":
        """Parse a compiled module's FLOPs / fused bytes / collective
        bytes with ``hlo_cost.module_cost`` (the while-body-aware text
        model). ``scale_by`` optionally names a config key that
        multiplies the cost (e.g. steps or tokens per job)."""
        from repro.roofline import hlo_cost
        mc = hlo_cost.module_cost(hlo_text)
        scale = ((lambda cfg: max(float(cfg.get(scale_by, 1.0)), 0.0))
                 if scale_by else (lambda cfg: 1.0))
        return cls(flops=lambda cfg: mc.flops * scale(cfg),
                   nbytes=lambda cfg: mc.bytes_fused * scale(cfg),
                   coll_bytes=lambda cfg: mc.coll_bytes * scale(cfg))


class RooflinePrior:
    """Cold-start runtime estimates per (template, accelerator family).

    ``hardware`` maps pool/family name -> :class:`HardwareSpec`;
    templates register analytic costs with :meth:`register` /
    :meth:`register_hlo`. :meth:`estimate` raises ``KeyError`` for an
    unknown template or family so callers (``Profiler.predict_for_pool``)
    can fall through to their own defaults.
    """

    def __init__(self, hardware: dict[str, HardwareSpec]):
        self.hardware = dict(hardware)
        self.templates: dict[str, TemplateCost] = {}

    def register(self, template: str, *, flops: CostFn = 0.0,
                 nbytes: CostFn = 0.0,
                 coll_bytes: CostFn = 0.0) -> "RooflinePrior":
        self.templates[template] = TemplateCost(flops, nbytes, coll_bytes)
        return self

    def register_hlo(self, template: str, hlo_text: str, *,
                     scale_by: Optional[str] = None) -> "RooflinePrior":
        self.templates[template] = TemplateCost.from_hlo(
            hlo_text, scale_by=scale_by)
        return self

    def can_estimate(self, template: str, family: str) -> bool:
        return template in self.templates and family in self.hardware

    def estimate(self, template: str, family: str, config: dict) -> float:
        """Prior runtime seconds; KeyError when template/family unknown."""
        tc = self.templates[template]
        hw = self.hardware[family]
        flops, nbytes, coll = tc.evaluate(config)
        return hw.startup_s + roofline_ceiling_s(
            flops, nbytes, hw, coll_bytes=coll, n_chips=hw.chips(config))
