"""Roofline-derived step-time oracle for TPU-job auto-provisioning
experiments (Tables 2/3 analog).

On a real cluster the profiler's training data comes from real runs; this
container is CPU-only, so the oracle predicts step time from the same
three-term roofline of ``roofline/analysis.py``, as a function of
(chips, hbm_gb):

  compute    = MODEL_FLOPS * remat_factor / (chips * PEAK)
  memory     = (3 * param_bytes + act_bytes(batch, seq) ) / (chips * HBM)
  collective = fsdp gather + grad reduce-scatter bytes / (chips * ICI)
               + a per-step latency floor that grows with chip count

  t_step = max(compute, memory, collective);  t_job = steps * t_step

remat_factor rises when per-chip HBM cannot hold the no-remat working set
(less memory -> recompute). Multiplicative log-normal noise models cloud
variance (paper §5.1: caching, multi-tenancy). The oracle's FUNCTIONAL
FORM is what the paper's log-linear model must fit — deliberately not a
pure power law (collective floor), mirroring the paper's observed CPU
non-linearity.
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np

from repro.configs.base import ArchConfig
from repro.configs.shapes import ShapeConfig
from repro.roofline.analysis import TARGET


def step_time(cfg: ArchConfig, shape: ShapeConfig, chips: float,
              hbm_gb: float, rng: Optional[np.random.Generator] = None,
              noise: float = 0.0) -> float:
    n = cfg.n_active_params()
    tokens = shape.global_batch * shape.seq_len
    param_bytes = 4.0 * cfg.n_params()
    act_bytes = 2.0 * tokens * cfg.d_model * 8       # boundary activations

    # remat need: fp32 params+moments+grads + activations must fit in the
    # usable fraction of the reservation; below that the job trains with
    # full activation recompute (4/3 compute)
    resident = 12.0 * cfg.n_params() / chips + act_bytes / chips
    budget = hbm_gb * 1e9
    remat = 1.0 if resident < 0.9 * budget else 4.0 / 3.0

    compute = 6.0 * n * tokens * remat / (chips * TARGET.peak_flops)
    memory = (3.0 * param_bytes + 4.0 * act_bytes) / (chips * TARGET.hbm_bw)
    # FSDP gather + gradient reduce-scatter: every device moves ~the full
    # parameter bytes per step REGARDLESS of chip count (ring collectives)
    # — the strong-scaling wall the provisioner must respect
    coll = (2.5 * param_bytes / TARGET.ici_bw
            + 2e-3 * math.log2(max(chips, 2)))       # latency floor
    t = max(compute, memory, coll)
    if noise and rng is not None:
        t *= math.exp(rng.normal(0.0, noise))
    return t


def job_time(cfg, shape, steps: float, chips: float, hbm_gb: float,
             rng=None, noise: float = 0.0) -> float:
    return steps * step_time(cfg, shape, chips, hbm_gb, rng, noise)
