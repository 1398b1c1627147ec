"""Pallas kernel block-size autotuner (ROADMAP item 2, perf_hillclimb idiom).

The four seed kernels (flash attention, decode attention, mamba2 SSD,
RWKV6) all expose block/chunk sizes chosen for the MXU's 128x128 systolic
array. The best size depends on the accelerator family and the problem
shape (VMEM working set vs grid-step overhead), so this module runs a
deterministic hillclimb over each kernel's candidate ladder, seeded from
the MXU-aligned defaults, and persists the winners in a tuning cache
(``BENCH_kernels.json``: best config + achieved fraction of the roofline
ceiling per (kernel, shape, family)).

Determinism: candidate measurements are memoized, neighbors are visited
in sorted parameter order, and a move requires beating the incumbent by
``HYSTERESIS`` — given the same measurements the search walks the same
path. Tests inject a synthetic ``measure`` function to pin the walk
exactly; CI runs the interpret-mode path (hermetic, no TPU) where
timings rank grid overhead rather than MXU behavior but every candidate
is still validated numerically against ``kernels/ref.py``.
"""
from __future__ import annotations

import dataclasses
import json
import math
import time
from typing import Callable, Optional

from repro.roofline.analysis import HardwareSpec, device_peaks
from repro.roofline.prior import roofline_ceiling_s

HYSTERESIS = 0.03        # a neighbor must win by >=3% to displace the
                         # incumbent — timing-noise damper + determinism
MAX_STEPS = 8            # hillclimb iterations (ladders are short)
BYTES_F32 = 4


# -- kernel registry -----------------------------------------------------
@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One tunable kernel: candidate ladders, input builder, reference.

    ``build(shape, seed)`` returns ``(args, ref_out)``;
    ``call(cfg, interpret, *args)`` runs the Pallas kernel;
    ``cost(shape)`` returns analytic (flops, hbm_bytes) for the roofline
    ceiling; ``divides_seq`` names params that must divide the sequence
    length (kernels whose grids cannot pad)."""
    name: str
    ladders: dict[str, tuple[int, ...]]
    default: dict[str, int]
    build: Callable[[dict, int], tuple]
    call: Callable[..., object]
    cost: Callable[[dict], tuple[float, float]]
    divides_seq: tuple[str, ...] = ()
    tol: float = 2e-2


def _keys(seed: int, n: int):
    import jax
    return jax.random.split(jax.random.PRNGKey(seed), n)


def _build_flash(shape: dict, seed: int):
    import jax
    from repro.kernels import ref
    b, s, h, kv, d = (shape[k] for k in ("b", "s", "h", "kv", "d"))
    ks = _keys(seed, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    return (q, k, v), ref.attention_ref(q, k, v)


def _call_flash(cfg, interpret, q, k, v):
    from repro.kernels import ops
    return ops.flash_attention(q, k, v, block_q=cfg["block_q"],
                               block_k=cfg["block_k"], interpret=interpret)


def _cost_flash(shape: dict) -> tuple[float, float]:
    b, s, h, kv, d = (shape[k] for k in ("b", "s", "h", "kv", "d"))
    flops = 4.0 * b * h * s * s * d * 0.5          # causal: half the pairs
    nbytes = BYTES_F32 * b * s * d * (2 * h + 2 * kv)   # q+o, k+v
    return flops, nbytes


def _build_decode(shape: dict, seed: int):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    b, s, h, kv, d = (shape[k] for k in ("b", "s", "h", "kv", "d"))
    ks = _keys(seed, 3)
    q = jax.random.normal(ks[0], (b, 1, h, d))
    kc = jax.random.normal(ks[1], (b, s, kv, d))
    vc = jax.random.normal(ks[2], (b, s, kv, d))
    clen = jnp.asarray([(s * 3) // 4 - 37 * i for i in range(b)], jnp.int32)
    want = ref.decode_attention_ref(
        jnp.swapaxes(q, 1, 2)[:, :, 0], jnp.swapaxes(kc, 1, 2),
        jnp.swapaxes(vc, 1, 2), clen)[:, None]
    return (q, kc, vc, clen), want


def _call_decode(cfg, interpret, q, kc, vc, clen):
    from repro.kernels import ops
    return ops.decode_attention(q, kc, vc, clen, block_k=cfg["block_k"],
                                interpret=interpret)


def _cost_decode(shape: dict) -> tuple[float, float]:
    b, s, h, kv, d = (shape[k] for k in ("b", "s", "h", "kv", "d"))
    flops = 4.0 * b * h * s * d
    nbytes = BYTES_F32 * b * s * d * 2 * kv        # the KV cache dominates
    return flops, nbytes


def _build_ssd(shape: dict, seed: int):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    b, s, h, p, n = (shape[k] for k in ("b", "s", "h", "p", "n"))
    ks = _keys(seed, 5)
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, 1, n)) * 0.5
    C = jax.random.normal(ks[4], (b, s, 1, n)) * 0.5
    D = jnp.ones((h,))
    return (x, dt, A, B, C, D), ref.ssd_ref(x, dt, A, B, C, D)


def _call_ssd(cfg, interpret, *args):
    from repro.kernels import ops
    return ops.mamba2_ssd(*args, chunk=cfg["chunk"], interpret=interpret)


def _cost_ssd(shape: dict) -> tuple[float, float]:
    b, s, h, p, n = (shape[k] for k in ("b", "s", "h", "p", "n"))
    chunk = 128
    flops = 2.0 * b * h * s * (chunk * (n + p) + 2 * n * p)
    nbytes = BYTES_F32 * b * s * (h * 2 * p + 2 * n + h)
    return flops, nbytes


def _build_wkv6(shape: dict, seed: int):
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    b, s, h, k = (shape[kk] for kk in ("b", "s", "h", "k"))
    ks = _keys(seed, 5)
    r = jax.random.normal(ks[0], (b, s, h, k)) * 0.5
    kk_ = jax.random.normal(ks[1], (b, s, h, k)) * 0.5
    v = jax.random.normal(ks[2], (b, s, h, k)) * 0.5
    logw = -jnp.exp(jax.random.uniform(ks[3], (b, s, h, k),
                                       minval=-7.0, maxval=-0.7))
    u = jax.random.normal(ks[4], (h, k)) * 0.3
    return (r, kk_, v, logw, u), ref.wkv6_ref(r, kk_, v, logw, u)


def _call_wkv6(cfg, interpret, *args):
    from repro.kernels import ops
    return ops.wkv6(*args, chunk=cfg["chunk"], interpret=interpret)


def _cost_wkv6(shape: dict) -> tuple[float, float]:
    b, s, h, k = (shape[kk] for kk in ("b", "s", "h", "k"))
    chunk = 128
    flops = 2.0 * b * h * s * (2 * chunk * k + 2 * k * k)
    nbytes = BYTES_F32 * b * s * h * k * 5
    return flops, nbytes


KERNELS: dict[str, KernelSpec] = {
    "flash_attention": KernelSpec(
        "flash_attention",
        ladders={"block_q": (32, 64, 128, 256),
                 "block_k": (32, 64, 128, 256)},
        default={"block_q": 128, "block_k": 128},
        build=_build_flash, call=_call_flash, cost=_cost_flash),
    "decode_attention": KernelSpec(
        "decode_attention",
        ladders={"block_k": (128, 256, 512, 1024)},
        default={"block_k": 512},
        build=_build_decode, call=_call_decode, cost=_cost_decode,
        divides_seq=("block_k",)),
    "mamba2_ssd": KernelSpec(
        "mamba2_ssd",
        ladders={"chunk": (32, 64, 128, 256)},
        default={"chunk": 128},
        build=_build_ssd, call=_call_ssd, cost=_cost_ssd,
        divides_seq=("chunk",)),
    "rwkv6": KernelSpec(
        "rwkv6",
        ladders={"chunk": (32, 64, 128, 256)},
        default={"chunk": 128},
        build=_build_wkv6, call=_call_wkv6, cost=_cost_wkv6,
        divides_seq=("chunk",)),
}


def legal(spec: KernelSpec, shape: dict, cfg: dict) -> bool:
    """A candidate is legal when every param is on its ladder, fits the
    sequence, and (for pad-less kernels) divides it."""
    s = shape["s"]
    for p, v in cfg.items():
        if v not in spec.ladders[p] or v > s:
            return False
        if p in spec.divides_seq and s % v:
            return False
    return True


def seed_config(spec: KernelSpec, shape: dict) -> dict:
    """The MXU-aligned default, stepped down each ladder until legal for
    this shape (e.g. chunk 128 -> 64 for a 192-long sequence)."""
    cfg = dict(spec.default)
    for p in cfg:
        ladder = spec.ladders[p]
        i = ladder.index(cfg[p])
        while i >= 0 and not legal(spec, shape, {**cfg, p: ladder[i]}):
            i -= 1
        if i < 0:
            raise ValueError(
                f"{spec.name}: no legal {p} for shape {shape}")
        cfg[p] = ladder[i]
    return cfg


# -- deterministic hillclimb --------------------------------------------
def hillclimb(spec: KernelSpec, shape: dict,
              measure: Callable[[dict], float], *,
              start: Optional[dict] = None,
              max_steps: int = MAX_STEPS) -> tuple[dict, float, int]:
    """Greedy coordinate descent from the seeded default: per step, time
    every +-1 ladder neighbor (sorted param order, memoized) and move to
    the best one iff it beats the incumbent by ``HYSTERESIS``. Returns
    (best_config, best_seconds, candidates_measured)."""
    memo: dict[tuple, float] = {}

    def key(cfg):
        return tuple(sorted(cfg.items()))

    def timed(cfg):
        k = key(cfg)
        if k not in memo:
            memo[k] = measure(cfg)
        return memo[k]

    cur = dict(start) if start else seed_config(spec, shape)
    cur_t = timed(cur)
    for _ in range(max_steps):
        best_cfg, best_t = cur, cur_t
        for p in sorted(spec.ladders):
            ladder = spec.ladders[p]
            i = ladder.index(cur[p])
            for j in (i - 1, i + 1):
                if not 0 <= j < len(ladder):
                    continue
                cand = {**cur, p: ladder[j]}
                if not legal(spec, shape, cand):
                    continue
                t = timed(cand)
                if t < best_t * (1.0 - HYSTERESIS):
                    best_cfg, best_t = cand, t
        if best_cfg == cur:
            break
        cur, cur_t = best_cfg, best_t
    return cur, cur_t, len(memo)


# -- measurement ---------------------------------------------------------
def _interpret_measure(spec: KernelSpec, args, *, interpret: bool,
                       reps: int = 3) -> Callable[[dict], float]:
    """Median-of-reps wall time per call (after a warm/compile call)."""
    import jax

    def measure(cfg: dict) -> float:
        jax.block_until_ready(spec.call(cfg, interpret, *args))
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(spec.call(cfg, interpret, *args))
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]
    return measure


def max_abs_err(spec: KernelSpec, args, ref_out, cfg: dict,
                interpret: bool) -> float:
    import jax.numpy as jnp
    out = spec.call(cfg, interpret, *args)
    return float(jnp.abs(out - ref_out).max())


def default_family() -> str:
    """The accelerator family tuning runs against: the device kind JAX
    reports, or ``interpret`` on a CPU host (Pallas interpret mode)."""
    import jax
    dev = jax.devices()[0]
    return "interpret" if dev.platform == "cpu" else dev.device_kind


# interpret-mode "hardware": CPU-interpreter constants so the recorded
# roofline fraction is well-defined (tiny — it measures the interpreter,
# not silicon) without pretending CI timings are TPU timings.
INTERPRET_HW = HardwareSpec("interpret", peak_flops=50e9, hbm_bw=20e9,
                            ici_bw=1.0)


def _family_hw(family: str) -> HardwareSpec:
    """Peaks for ``family``; a device kind without published peaks
    raises."""
    if family == "interpret":
        return INTERPRET_HW
    return device_peaks(family)


# -- the tuning cache ----------------------------------------------------
def shape_key(shape: dict) -> str:
    return ",".join(f"{k}={shape[k]}" for k in sorted(shape))


def cache_key(kernel: str, shape: dict, family: str) -> str:
    return f"{kernel}|{shape_key(shape)}|{family}"


class TuningCache:
    """Persisted (kernel, shape, family) -> tuning entry map.

    The JSON layout is the committed ``BENCH_kernels.json``: a dict of
    ``kernel|shape|family`` keys, each holding the winning config, the
    timings that won it, the achieved fraction of the roofline ceiling,
    and the max error vs the reference kernel."""

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.entries: dict[str, dict] = {}
        if path:
            self.load(path)

    def load(self, path: str) -> "TuningCache":
        self.path = path
        try:
            with open(path) as f:
                blob = json.load(f)
            self.entries = dict(blob.get("entries", blob))
        except (OSError, json.JSONDecodeError):
            self.entries = {}
        return self

    def save(self, path: Optional[str] = None) -> None:
        path = path or self.path
        assert path, "TuningCache.save: no path"
        with open(path, "w") as f:
            json.dump({"entries": dict(sorted(self.entries.items()))},
                      f, indent=1, sort_keys=True)

    def put(self, entry: dict) -> None:
        self.entries[cache_key(entry["kernel"], entry["shape"],
                               entry["family"])] = entry

    def get(self, kernel: str, shape: dict,
            family: str) -> Optional[dict]:
        return self.entries.get(cache_key(kernel, shape, family))

    def best_config(self, kernel: str, shape: dict, family: str,
                    default: Optional[dict] = None) -> Optional[dict]:
        """The tuned config for an exact (kernel, shape, family) hit,
        else ``default`` (callers pass the kernel's MXU default)."""
        e = self.get(kernel, shape, family)
        return dict(e["config"]) if e else default


# -- the tuner entry point ----------------------------------------------
def autotune(kernel: str, shape: dict, *,
             family: Optional[str] = None, interpret: bool = True,
             seed: int = 0, reps: int = 3,
             measure: Optional[Callable[[dict], float]] = None,
             cache: Optional[TuningCache] = None) -> dict:
    """Tune one (kernel, shape) for ``family`` and return (and cache)
    the tuning entry. ``measure`` overrides the timing function (tests
    inject deterministic synthetic costs)."""
    spec = KERNELS[kernel]
    family = family or default_family()
    args, ref_out = spec.build(shape, seed)
    if measure is None:
        measure = _interpret_measure(spec, args, interpret=interpret,
                                     reps=reps)
    default = seed_config(spec, shape)
    # one memoized timing per config, shared between the default
    # measurement and the hillclimb: the same config must never carry
    # two (noisy) timings, or speedup_vs_default could dip below 1.0
    # for the config the climb never left
    memo: dict[tuple, float] = {}

    def timed(cfg: dict) -> float:
        k = tuple(sorted(cfg.items()))
        if k not in memo:
            memo[k] = measure(cfg)
        return memo[k]

    default_t = timed(default)
    best, best_t, n_meas = hillclimb(spec, shape, timed, start=default)
    err = max_abs_err(spec, args, ref_out, best, interpret)
    hw = _family_hw(family)
    flops, nbytes = spec.cost(shape)
    ceiling = roofline_ceiling_s(flops, nbytes, hw)
    entry = {
        "kernel": kernel, "shape": dict(shape), "family": family,
        "config": best, "default_config": default,
        "us": best_t * 1e6, "default_us": default_t * 1e6,
        "speedup_vs_default": default_t / max(best_t, 1e-12),
        "candidates_measured": n_meas,
        "roofline_ceiling_us": ceiling * 1e6,
        "roofline_fraction": ceiling / max(best_t, 1e-12),
        "max_err": err, "tol": spec.tol,
        "mode": "interpret" if interpret else "compiled",
    }
    assert err <= spec.tol, \
        f"{kernel}{shape}: tuned config {best} diverges from ref " \
        f"(err {err:.3e} > {spec.tol})"
    if not math.isfinite(best_t):
        raise RuntimeError(f"{kernel}: non-finite timing")
    if cache is not None:
        cache.put(entry)
    return entry


# shapes the bench/CI smoke tunes — small enough for interpret mode,
# ragged/odd-head-dim cases included on purpose (they exercise the
# flash padding path the tuner depends on)
SMOKE_SHAPES: dict[str, list[dict]] = {
    "flash_attention": [
        {"b": 1, "s": 256, "h": 4, "kv": 2, "d": 64},
        {"b": 1, "s": 192, "h": 2, "kv": 2, "d": 80},
    ],
    "decode_attention": [{"b": 2, "s": 1024, "h": 4, "kv": 2, "d": 64}],
    "mamba2_ssd": [{"b": 1, "s": 256, "h": 4, "p": 64, "n": 32}],
    "rwkv6": [{"b": 1, "s": 256, "h": 2, "k": 64}],
}


def autotune_all(*, family: Optional[str] = None, interpret: bool = True,
                 seed: int = 0, reps: int = 3,
                 shapes: Optional[dict[str, list[dict]]] = None,
                 cache: Optional[TuningCache] = None) -> list[dict]:
    shapes = shapes or SMOKE_SHAPES
    out = []
    for kernel, shape_list in shapes.items():
        for shape in shape_list:
            out.append(autotune(kernel, shape, family=family,
                                interpret=interpret, seed=seed,
                                reps=reps, cache=cache))
    return out
