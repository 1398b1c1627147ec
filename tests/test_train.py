"""Training substrate: optimizer, train step, checkpoints, fault-tolerant
supervision, data pipeline."""
import io
import json
import time
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.core.acai import AcaiProject
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.models import model as M
from repro.train.checkpoints import CheckpointManager
from repro.train.fault import JobPreempted, TrainSupervisor
from repro.train.optimizer import (OptimizerConfig, adamw_update,
                                   init_opt_state, schedule)
from repro.train.train_step import TrainConfig, make_train_step


def test_adamw_converges_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    opt = init_opt_state(params)
    cfg = OptimizerConfig(lr=0.1, warmup_steps=0, total_steps=200,
                          weight_decay=0.0)
    for _ in range(200):
        grads = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
        params, opt, _ = adamw_update(cfg, params, grads, opt)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_schedule_shape():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_frac=0.1)
    lrs = [float(schedule(cfg, jnp.array(s))) for s in (0, 5, 10, 100)]
    assert lrs[0] == 0.0
    assert lrs[1] == pytest.approx(0.5)
    assert lrs[2] == pytest.approx(1.0)
    assert lrs[3] == pytest.approx(0.1)


def test_grad_clipping():
    params = {"w": jnp.zeros(3)}
    opt = init_opt_state(params)
    cfg = OptimizerConfig(lr=1e-3, clip_norm=1.0, warmup_steps=0)
    huge = {"w": jnp.full(3, 1e6)}
    _, _, m = adamw_update(cfg, params, huge, opt)
    assert float(m["grad_norm"]) > 1e5   # reported pre-clip


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_adamw_keeps_each_leaf_at_its_storage_dtype(dtype):
    """In a tree of a float32 and a bfloat16 leaf, the leaf of ``dtype``
    comes back at its own dtype, moved by the fp32 update of its values
    rounded to that dtype; its moments stay fp32."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(0, 1, (8, 16)), jnp.float32)
    g = jnp.asarray(rng.normal(0, 1, (8, 16)), jnp.float32)
    params = {"float32": w, "bfloat16": w.astype(jnp.bfloat16)}
    grads = {"float32": g, "bfloat16": g.astype(jnp.bfloat16)}
    # no clipping: each leaf's update is then its own
    cfg = OptimizerConfig(lr=1e-2, warmup_steps=0, weight_decay=0.1,
                          clip_norm=1e9)
    new, opt, _ = adamw_update(cfg, params, grads, init_opt_state(params))
    p = params[dtype]
    assert new[dtype].dtype == p.dtype
    assert opt["mu"][dtype].dtype == opt["nu"][dtype].dtype == jnp.float32
    one = {"w": p.astype(jnp.float32)}
    ref, _, _ = adamw_update(cfg, one,
                             {"w": grads[dtype].astype(jnp.float32)},
                             init_opt_state(one))
    np.testing.assert_array_equal(np.asarray(new[dtype], np.float32),
                                  np.asarray(ref["w"].astype(p.dtype),
                                             np.float32))
    assert not np.array_equal(np.asarray(new[dtype], np.float32),
                              np.asarray(p, np.float32))


def _tiny_setup():
    cfg = get_arch("olmo-1b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tcfg = TrainConfig()
    ocfg = OptimizerConfig(lr=3e-3, warmup_steps=5, total_steps=100,
                           weight_decay=0.0)
    step = make_train_step(cfg, tcfg, ocfg)
    # data vocab << model vocab: fast-learnable structure for the assertion
    pipe = TokenPipeline(DataConfig(vocab_size=32, seq_len=32,
                                    global_batch=16, markov_temp=2.5), cfg)
    return cfg, params, tcfg, step, pipe


def test_train_loss_decreases():
    cfg, params, tcfg, step, pipe = _tiny_setup()
    opt = init_opt_state(params)
    step = jax.jit(step)
    losses = []
    for i in range(25):
        batch = jax.tree.map(jnp.asarray, pipe.batch_at(i))
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 1.0, losses


def test_checkpoint_roundtrip(tmp_path):
    proj = AcaiProject("p", tmp_path)
    ckpt = CheckpointManager(proj, "run1")
    params = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
              "nested": {"b": jnp.ones(4, jnp.bfloat16)}}
    opt = init_opt_state(params)
    ref = ckpt.save(5, params, opt, extra={"loss": 1.5})
    assert ref.endswith(":1")
    state, step = ckpt.restore({"params": params, "opt": opt})
    assert step == 5
    np.testing.assert_array_equal(np.asarray(state["params"]["w"]),
                                  np.asarray(params["w"]))
    # versioned history: second save -> version 2, both restorable
    params2 = jax.tree.map(lambda a: a + 1, params)
    ckpt.save(9, params2, opt)
    s2, st2 = ckpt.restore({"params": params, "opt": opt})
    assert st2 == 9
    s1, st1 = ckpt.restore({"params": params, "opt": opt}, version=1)
    assert st1 == 5
    np.testing.assert_array_equal(np.asarray(s1["params"]["w"]),
                                  np.asarray(params["w"]))
    # provenance: checkpoint registered in metadata with its step
    assert proj.metadata.get(f"run1-ckpt:2")["step"] == 9


def _mixed_tree():
    """float32, bfloat16, int32 and 0-d leaves, one of them empty."""
    return {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) / 7,
            "half": {"b": (jnp.arange(5, dtype=jnp.float32) / 3
                           ).astype(jnp.bfloat16)},
            "ids": jnp.array([[1, -2], [3, 2 ** 30]], jnp.int32),
            "scale": jnp.array(0.125, jnp.float32),
            "count": jnp.array(7, jnp.int32),
            "none": jnp.zeros((0, 3), jnp.float32)}


def _assert_same(got, want):
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape, path
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=str(path))


def test_checkpoint_roundtrip_keeps_every_dtype(tmp_path):
    cfg = get_arch("olmo-1b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    opt = init_opt_state(params)
    ckpt = CheckpointManager(AcaiProject("p", tmp_path), "mixed")
    ref = ckpt.save(3, {"model": params, "mixed": _mixed_tree()}, opt)
    template = {"params": {"model": params, "mixed": _mixed_tree()},
                "opt": opt}
    state, step = ckpt.restore(template)
    assert step == 3
    _assert_same(state, template)
    meta = ckpt.project.metadata.get(ref)
    storage = ckpt.project.storage
    fsv = ckpt.project.filesets.resolve(ref)
    blob = storage.resolve("/mixed-ckpt/state.bin",
                           fsv.files["/mixed-ckpt/state.bin"])
    leaves = jax.tree.leaves(template)
    assert meta["bytes"] == blob.size == sum(np.asarray(a).nbytes
                                             for a in leaves)
    assert meta["hash_wait_s"] >= 0
    # a float32 template widens the leaf saved as bfloat16
    wide = jax.tree.map(lambda a: a.astype(jnp.float32)
                        if a.dtype == jnp.bfloat16 else a, template)
    state, _ = ckpt.restore(wide)
    _assert_same(state, wide)


def test_checkpoint_restores_the_old_npz_layout(tmp_path):
    """A fileset written as ``state.npz`` (bfloat16 widened to float32,
    a manifest with no layout) by earlier versions still restores."""
    proj = AcaiProject("p", tmp_path)
    tree = _mixed_tree()
    state = {"params": tree}
    flat = {"/".join(str(getattr(p, "key", p)) for p in path):
            np.asarray(leaf.astype(jnp.float32) if leaf.dtype == jnp.bfloat16
                       else leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]}
    buf = io.BytesIO()
    np.savez(buf, **flat)
    paths = ["/old-ckpt/state.npz", "/old-ckpt/manifest.json"]
    sid = proj.storage.begin_session(paths, creator="trainer")
    proj.storage.session_put(sid, paths[0], buf.getvalue())
    proj.storage.session_put(sid, paths[1], json.dumps(
        {"step": 11, "keys": sorted(flat), "extra": {}}).encode())
    fvs = proj.storage.commit_session(sid)
    proj.filesets.create("old-ckpt", [f"{fv.path}@{fv.version}"
                                      for fv in fvs])
    restored, step = CheckpointManager(proj, "old").restore(state)
    assert step == 11
    _assert_same(restored, state)


def test_checkpoint_save_holds_no_second_copy_of_the_state(tmp_path):
    """A save of a 64 MB state allocates on the host less than 0.3x the
    state's size beyond the one host copy any save makes (``np.asarray``
    of each leaf: the whole state on a TPU, nothing on the CPU backend,
    whose arrays are host memory already): 1.3x on a TPU. A whole-state
    bytes object (``np.savez`` into memory, ``getvalue``) fails it."""
    params = {f"w{i}": jnp.full((1 << 20, 2), i, jnp.float32)
              for i in range(8)}
    nbytes = sum(a.nbytes for a in params.values())
    assert nbytes == 64 << 20
    ckpt = CheckpointManager(AcaiProject("p", tmp_path), "mem")
    ckpt.save(0, {"warm": jnp.zeros(4)})
    tracemalloc.start()
    try:
        host = [np.asarray(a) for a in params.values()]
        _, fetched = tracemalloc.get_traced_memory()
        del host
        tracemalloc.reset_peak()
        ckpt.save(1, params)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert peak < fetched + 0.3 * nbytes, (peak / nbytes, fetched / nbytes)
    state, _ = ckpt.restore({"params": params})
    _assert_same(state, {"params": params})


def test_supervisor_restart_and_stragglers(tmp_path):
    proj = AcaiProject("p", tmp_path)
    ckpt = CheckpointManager(proj, "runF")
    sup = TrainSupervisor(ckpt, save_every=5, straggler_factor=3.0)

    params = {"w": jnp.zeros(2)}
    opt = init_opt_state(params)

    def step_fn(params, opt, batch):
        grads = {"w": jnp.ones(2)}
        p, o, _ = adamw_update(OptimizerConfig(lr=0.1, warmup_steps=0),
                               params, grads, opt)
        return p, o, {"loss": jnp.sum(p["w"] ** 2)}

    fails = {12}
    def failure_hook(step):
        if step in fails:
            fails.discard(step)
            raise JobPreempted(f"node died at {step}")

    # time_fn is read at the start, at each step's completion and after
    # each save; the tenth reading ends one step's interval 0.5 s after
    # the previous completion -> one straggler step
    clock = iter(np.concatenate([np.ones(9) * 0.01, [0.5],
                                 np.ones(100) * 0.01]).cumsum())
    state, report = sup.run(step_fn, {"params": params, "opt": opt,
                                      "step": 0},
                            n_steps=20, batch_fn=lambda s: {},
                            failure_hook=failure_hook,
                            time_fn=lambda: next(clock))
    assert state["step"] == 20
    assert report.restarts == 1
    # resumed from step 10 checkpoint, not from scratch
    assert report.steps_run == 20 + (12 - 10)
    assert report.checkpoints >= 4
    assert len(report.straggler_steps) >= 1


def test_supervisor_times_each_step_and_flags_the_slow_one(tmp_path):
    sup = TrainSupervisor(CheckpointManager(AcaiProject("p", tmp_path),
                                            "runT"), save_every=100)
    slow, n_steps = 6, 10

    def step_fn(params, opt, batch):
        time.sleep(0.4 if batch["i"] == slow else 0.02)
        return params, opt, {"loss": jnp.zeros(())}

    params = {"w": jnp.zeros(2)}
    state, report = sup.run(step_fn, {"params": params,
                                      "opt": init_opt_state(params),
                                      "step": 0},
                            n_steps=n_steps, batch_fn=lambda i: {"i": i})
    assert state["step"] == n_steps
    assert len(report.step_s) == n_steps
    assert slow in report.straggler_steps
    assert slow - 1 not in report.straggler_steps
    assert report.step_s[slow] >= 0.4
    assert len(report.save_s) == report.checkpoints == 1


def test_pipeline_determinism_and_sharding():
    base = DataConfig(seed=7, vocab_size=64, seq_len=16, global_batch=8,
                      n_hosts=2, host_index=0)
    p0 = TokenPipeline(base)
    p0b = TokenPipeline(base)
    np.testing.assert_array_equal(p0.batch_at(3)["tokens"],
                                  p0b.batch_at(3)["tokens"])
    import dataclasses as dc
    p1 = TokenPipeline(dc.replace(base, host_index=1))
    assert not np.array_equal(p0.batch_at(3)["tokens"],
                              p1.batch_at(3)["tokens"])
    # labels are next-token shifted
    b = p0.batch_at(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])
