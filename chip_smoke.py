#!/usr/bin/env python3
"""Bring-up smoke run of ACAI's main path on TPU.

    python3 chip_smoke.py [--seed N]                # one chip
    python3 chip_smoke.py --four-chips [--seed N]   # one host, four chips

On one chip, four phases run in this one process, through the entry
points a user calls:

  device   a TPU is attached (there is no CPU fallback) and its device
           kind has published peaks (``roofline/analysis.py``);
  kernels  the four Pallas kernels, compiled and never interpreted, at
           real widths, against their ``kernels/ref.py`` oracles;
  serve    olmo-1b at published widths and full depth with random
           weights, through ``launch/serve.py``'s continuous-batching
           loop; each prompt's last-position logits agree with
           ``M.prefill`` on the same tokens;
  train    a few olmo-1b steps (full width, depth cut to 4 of 16 layers)
           submitted as a job through ``AcaiEngine``'s in-process runner,
           checkpointed into the data lake; the checkpoint restores.

``--four-chips`` runs only the sharded path: the olmo-1b train step
through ``build_sharded_train`` on a 2x2 ("data", "model") mesh against
the same steps on one device, then a few steps at full depth.

Weights and data come from ``--seed``. The times printed are set-up and
smoke wall times (compilation included), not metrics. The last line of
stdout is one JSON object, ``{"ok": true, "device": {...}}``; a failed
phase exits nonzero without it. Scratch state goes to ``.chip_smoke/``
and the compile cache to ``launch/compile_cache.py``'s directory.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import shutil
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
if not (ROOT / "src" / "repro").is_dir():
    sys.exit("chip_smoke.py runs from a checkout of the repository "
             "(src/repro not found beside it)")
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_arch  # noqa: E402
from repro.core.acai import AcaiEngine, AcaiProject  # noqa: E402
from repro.core.engine.lifecycle import JobState  # noqa: E402
from repro.core.engine.registry import JobSpec  # noqa: E402
from repro.data.pipeline import DataConfig, TokenPipeline  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.serve import serve  # noqa: E402
from repro.launch.train import init_train, train  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.roofline.analysis import device_peaks  # noqa: E402
from repro.serve.decode import make_prefill_step  # noqa: E402
from repro.sharding import rules as SR  # noqa: E402
from repro.sharding.mesh import make_mesh  # noqa: E402
from repro.train.checkpoints import CheckpointManager  # noqa: E402
from repro.train.optimizer import OptimizerConfig  # noqa: E402
from repro.train.train_step import TrainConfig, make_opt_state  # noqa: E402

WORKDIR = ROOT / ".chip_smoke"
ARCH = "olmo-1b"
# Full-depth olmo-1b AdamW state (1.18 B params x 16 B, ~18.8 GB) exceeds a
# v5e's 16 GB, so one chip trains 4 of the 16 layers at full width. At
# B=8, S=1024 the v5e compiler's memory_analysis gives 4.46 GB of
# arguments and 4.44 GB of temporaries for that step.
TRAIN_LAYERS = 4
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 5
DATA_VOCAB = 64          # the synthetic Markov stream's alphabet

# Tolerances, each relative to max(1, max |reference|):
# bf16 kernels take bf16 in and give bf16 out; both sides run fp32
# softmax, so they differ by up to two bf16 roundings (2^-8 each) of the
# largest output plus fp32 summation order.
TOL_BF16_KERNEL = 2e-2
# fp32 recurrences: the kernels sum 128-token chunks as MXU matmuls, the
# oracles run a 4096-step sequential scan, so only rounding order differs.
TOL_F32_SCAN = 1e-3
# serve: decode and prefill both run bf16 through 16 layers, but with
# different attention code (cached one-token decode vs full causal) and
# cache round trips; a wrong position, mask or cache slot moves the
# logits by O(1) of their scale, far above this.
TOL_SERVE_LOGITS = 5e-2
# sharded vs one-device losses: bf16 activations with matmuls and the
# loss reduced in another order over the mesh; five AdamW steps at
# lr <= 3e-3 keep the trajectories this close.
TOL_SHARDED_LOSS = 2e-3


def _max_err(got, want) -> tuple[float, float]:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values in the output")
    return (float(np.abs(got - want).max()),
            max(1.0, float(np.abs(want).max())))


def _check(name: str, got, want, tol: float) -> None:
    err, scale = _max_err(got, want)
    ok = err <= tol * scale
    print(f"  {name}: max|err| {err:.3e} vs tol {tol * scale:.3e} "
          f"({'ok' if ok else 'FAIL'})", flush=True)
    if not ok:
        raise AssertionError(f"{name}: max|err| {err:.3e} > "
                             f"{tol * scale:.3e}")


# -- phases --------------------------------------------------------------
def check_device(count: int):
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind!r} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"no TPU attached: JAX found {d.platform!r}")
    if len(devs) < count:
        raise SystemExit(f"need {count} chips, JAX found {len(devs)}")
    hw = device_peaks(d.device_kind)
    print(f"  peaks: {hw.peak_flops:.4g} FLOP/s bf16, {hw.hbm_bw:.4g} B/s "
          f"HBM", flush=True)
    return d, len(devs)


def check_kernels(seed: int, *, seq: int = 4096, slots: int = 8,
                  cache: int = 2048) -> None:
    """Each kernel compiled (interpret=False) at real widths against its
    oracle, which runs at full fp32 matmul precision."""
    olmo, rwkv, zamba = (get_arch(n) for n in
                         ("olmo-1b", "rwkv6-7b", "zamba2-7b"))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def normal(shape, dtype=jnp.float32, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    bf16 = jnp.bfloat16
    h, kv, d = olmo.n_heads, olmo.n_kv_heads, olmo.resolved_head_dim
    print(f"  olmo-1b heads: {h} q / {kv} kv of {d}", flush=True)
    q = normal((1, seq, h, d), bf16)
    k = normal((1, seq, kv, d), bf16)
    v = normal((1, seq, kv, d), bf16)
    got = ops.flash_attention(q, k, v, causal=True, interpret=False)
    with jax.default_matmul_precision("highest"):
        want = ref.attention_ref(q, k, v, causal=True)
    _check(f"flash_attention B=1 S={seq}", got, want, TOL_BF16_KERNEL)

    q = normal((slots, 1, h, d), bf16)
    kc = normal((slots, cache, kv, d), bf16)
    vc = normal((slots, cache, kv, d), bf16)
    lens = jax.random.randint(next(keys), (slots,), 1, cache + 1)
    got = ops.decode_attention(q, kc, vc, lens, interpret=False)
    with jax.default_matmul_precision("highest"):
        want = ref.decode_attention_ref(q[:, 0], jnp.swapaxes(kc, 1, 2),
                                        jnp.swapaxes(vc, 1, 2), lens)
    _check(f"decode_attention B={slots} cache={cache}", got[:, 0], want,
           TOL_BF16_KERNEL)

    hd = rwkv.rwkv.head_dim
    nh = rwkv.d_model // hd
    print(f"  rwkv6-7b wkv: {nh} heads of {hd}", flush=True)
    r, kk, vv = (normal((1, seq, nh, hd), scale=0.5) for _ in range(3))
    # RWKV decay magnitudes: logw in (-0.5, -1e-3)
    logw = -jnp.exp(jax.random.uniform(next(keys), (1, seq, nh, hd),
                                       minval=-7.0, maxval=-0.7))
    u = normal((nh, hd), scale=0.3)
    got = ops.wkv6(r, kk, vv, logw, u, interpret=False)
    with jax.default_matmul_precision("highest"):
        want = ref.wkv6_ref(r, kk, vv, logw, u)
    _check(f"wkv6 S={seq}", got, want, TOL_F32_SCAN)

    mc = zamba.mamba
    nh, p, n, g = (mc.n_heads(zamba.d_model), mc.head_dim, mc.d_state,
                   mc.n_groups)
    print(f"  zamba2-7b ssd: {nh} heads of {p}, state {n}, {g} group",
          flush=True)
    x = normal((1, seq, nh, p), scale=0.5)
    dt = jax.nn.softplus(normal((1, seq, nh)) - 1.0)
    a = -jnp.exp(normal((nh,), scale=0.3))
    bm = normal((1, seq, g, n), scale=0.5)
    cm = normal((1, seq, g, n), scale=0.5)
    dd = jnp.ones((nh,))
    got = ops.mamba2_ssd(x, dt, a, bm, cm, dd, interpret=False)
    with jax.default_matmul_precision("highest"):
        want = ref.ssd_ref(x, dt, a, bm, cm, dd)
    _check(f"mamba2_ssd S={seq}", got, want, TOL_F32_SCAN)


def check_serve(cfg, seed: int, *, slots: int = 8, n_requests: int = 12,
                prompt_len: int = 64, max_new: int = 16,
                buffer_len: int = 1024) -> None:
    print(f"  {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"vocab {cfg.vocab_size}; {n_requests} requests over {slots} "
          f"slots, buffer {buffer_len}", flush=True)
    params = jax.jit(functools.partial(M.init_params, cfg))(
        jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (n_requests, prompt_len)).tolist()
    res = serve(cfg, params, prompts, slots=slots, buffer_len=buffer_len,
                max_new=max_new)
    for r, toks in res.produced.items():
        if len(toks) != max_new or not all(0 <= t < cfg.vocab_size
                                           for t in toks):
            raise AssertionError(f"request {r} produced {toks}")
    print(f"  served {n_requests} requests in {res.ticks} decode steps",
          flush=True)
    prefill = jax.jit(make_prefill_step(cfg))
    want = prefill(params, {"tokens": jnp.asarray(prompts, jnp.int32)})
    got = np.stack([res.prompt_logits[r] for r in range(n_requests)])
    same = int((got.argmax(-1) == np.asarray(want).argmax(-1)).sum())
    print(f"  greedy first token equal to prefill's in {same}/{n_requests}",
          flush=True)
    _check("decode vs M.prefill logits at the last prompt position", got,
           want, TOL_SERVE_LOGITS)


_checksum = jax.jit(lambda tree: sum(jnp.sum(jnp.abs(x.astype(jnp.float32)))
                                     for x in jax.tree.leaves(tree)))


def check_train(cfg, seed: int, workdir: Path) -> None:
    print(f"depth cut: {cfg.name} trained at {cfg.n_layers} of "
          f"{get_arch(ARCH).n_layers} layers, full width (d_model "
          f"{cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size})",
          flush=True)
    shutil.rmtree(workdir, ignore_errors=True)
    project = AcaiProject("chip-smoke", workdir / "lake")
    engine = AcaiEngine(datalake=project, workroot=str(workdir / "jobs"),
                        runner="local")
    run = f"{cfg.name}-smoke"

    def train_job(wd, job):
        res = train(cfg, project, run, steps=TRAIN_STEPS, seq_len=TRAIN_SEQ,
                    global_batch=TRAIN_BATCH, data_vocab=DATA_VOCAB,
                    save_every=TRAIN_STEPS, seed=seed)
        return {"losses": res.losses, "step": res.state["step"],
                "checkpoints": res.report.checkpoints,
                "checksum": float(_checksum(res.state["params"]))}

    handle = engine.submit(JobSpec(name=run, project="chip-smoke",
                                   user="chip_smoke", fn=train_job))
    state = handle.wait()
    if state != JobState.FINISHED:
        raise RuntimeError(f"train job ended {state.value}:\n"
                           f"{handle.job.error}")
    out = handle.result()
    losses = out["losses"]
    print(f"  job {handle.job_id} {state.value}: {out['step']} steps "
          f"(B={TRAIN_BATCH}, S={TRAIN_SEQ}), losses "
          f"{[round(x, 4) for x in losses]}", flush=True)
    if len(losses) != TRAIN_STEPS or not np.isfinite(losses).all():
        raise AssertionError(f"losses {losses}")
    tcfg = TrainConfig()
    shapes = jax.eval_shape(functools.partial(M.init_params, cfg),
                            jax.random.PRNGKey(seed))
    template = {"params": shapes,
                "opt": jax.eval_shape(functools.partial(
                    make_opt_state, tcfg=tcfg), shapes)}
    restored, step = CheckpointManager(project, run).restore(template)
    checksum = float(_checksum(restored["params"]))
    print(f"  checkpoint restored at step {step}; params checksum "
          f"{checksum:.6e} (job's {out['checksum']:.6e})", flush=True)
    if step != TRAIN_STEPS or checksum != out["checksum"]:
        raise AssertionError("restored checkpoint differs from the run")


def _run_steps(cfg, mesh, batches, seed: int):
    tcfg = TrainConfig()
    ocfg = OptimizerConfig(lr=3e-3, warmup_steps=5,
                           total_steps=len(batches), weight_decay=0.0)
    SR.set_rules(None)     # build_sharded_train installs its mesh's rules
    step, params, opt = init_train(cfg, tcfg, ocfg, mesh=mesh, seed=seed)
    losses = []
    for b in batches:
        params, opt, metrics = step(params, opt, b)
        losses.append(metrics["loss"])
    return [float(x) for x in losses], (params, opt)


def check_four_chips(seed: int, *, steps: int = 5, full_steps: int = 3):
    full = get_arch(ARCH)
    cut = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    pipe = TokenPipeline(DataConfig(seed=seed, vocab_size=DATA_VOCAB,
                                    seq_len=TRAIN_SEQ,
                                    global_batch=TRAIN_BATCH,
                                    markov_temp=2.5), cut)
    batches = [jax.tree.map(jnp.asarray, pipe.batch_at(i))
               for i in range(steps)]
    mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])

    t0 = time.perf_counter()
    one, _ = _run_steps(cut, None, batches, seed)
    print(f"  one device, {cut.n_layers} layers: losses "
          f"{[round(x, 5) for x in one]}", flush=True)
    four, _ = _run_steps(cut, mesh, batches, seed)
    print(f"  2x2 mesh, {cut.n_layers} layers: losses "
          f"{[round(x, 5) for x in four]}", flush=True)
    _check("sharded vs one-device losses", four, one, TOL_SHARDED_LOSS)
    print(f"[set-up/smoke wall time] 4-layer comparison: "
          f"{time.perf_counter() - t0:.1f} s", flush=True)

    t0 = time.perf_counter()
    deep, state = _run_steps(full, mesh, batches[:full_steps], seed)
    print(f"  2x2 mesh, {full.n_layers} layers (full depth): losses "
          f"{[round(x, 5) for x in deep]}", flush=True)
    if not np.isfinite(deep).all():
        raise AssertionError(f"full-depth losses {deep}")
    for dev in mesh.devices.flat:
        ms = dev.memory_stats() or {}
        print(f"  device {dev.id}: bytes_in_use "
              f"{ms.get('bytes_in_use', 0) / 1e9:.3f} GB, peak "
              f"{ms.get('peak_bytes_in_use', 0) / 1e9:.3f} GB", flush=True)
    del state
    print(f"[set-up/smoke wall time] full-depth steps: "
          f"{time.perf_counter() - t0:.1f} s", flush=True)


# -- driver --------------------------------------------------------------
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train path on a 2x2 mesh")
    args = ap.parse_args()

    use_compile_cache()
    t0 = time.perf_counter()
    dev, count = check_device(4 if args.four_chips else 1)
    print(f"[set-up/smoke wall time] device: "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    if args.four_chips:
        phases = [("four-chip train", lambda: check_four_chips(args.seed))]
    else:
        cut = dataclasses.replace(get_arch(ARCH), n_layers=TRAIN_LAYERS)
        phases = [
            ("kernels", lambda: check_kernels(args.seed)),
            ("serve", lambda: check_serve(get_arch(ARCH), args.seed)),
            ("train", lambda: check_train(cut, args.seed, WORKDIR)),
        ]
    failed = []
    for name, run in phases:
        print(f"== {name}", flush=True)
        t0 = time.perf_counter()
        try:
            run()
        except Exception:  # noqa: BLE001 — report, go on, exit nonzero
            traceback.print_exc()
            failed.append(name)
        print(f"[set-up/smoke wall time] {name}: "
              f"{time.perf_counter() - t0:.1f} s "
              f"({'FAILED' if name in failed else 'passed'})", flush=True)
    if failed:
        print(f"FAILED phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
