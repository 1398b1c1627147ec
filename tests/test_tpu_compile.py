"""Compiles for a described TPU v5e (a 2x2 host) with no chip attached.

Nothing runs: the TPU compiler refuses here what the chip would refuse
(block tiling, VMEM, a program that does not fit HBM). Covered: the four
Pallas kernels at real widths, the olmo-1b serve step at full width and
depth, the generation cell's serve step writing its donated cache in
place, the olmo-1b decode step with its cache sharded over the 2x2 mesh,
the olmo-1b train step sharded over the 2x2 mesh, the MoE cell's train
step on one chip, and its expert-parallel step over the 2x2 mesh.

The topology is described inside a module fixture, never at import, so
only the worker that runs this file loads the TPU compiler.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs.base import get_arch
from repro.kernels import ops

HBM_BYTES = 16e9            # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")    # no compiler logs in /tmp
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name, sds):
    """(fn, args) for one kernel at the widths of the config it serves."""
    bf16, f32, seq = jnp.bfloat16, jnp.float32, 4096
    if name in ("flash_attention", "decode_attention"):
        c = get_arch("olmo-1b")
        h, kv, d = c.n_heads, c.n_kv_heads, c.resolved_head_dim
        if name == "flash_attention":
            return ops.flash_attention, (sds((1, seq, h, d), bf16),
                                         sds((1, seq, kv, d), bf16),
                                         sds((1, seq, kv, d), bf16))
        cache = (8, 2048, kv, d)
        return ops.decode_attention, (sds((8, 1, h, d), bf16),
                                      sds(cache, bf16), sds(cache, bf16),
                                      sds((8,), jnp.int32))
    if name == "wkv6":
        c = get_arch("rwkv6-7b")
        k = c.rwkv.head_dim
        h = c.d_model // k
        return ops.wkv6, (sds((1, seq, h, k), f32),) * 4 + (sds((h, k), f32),)
    c = get_arch("zamba2-7b")
    mc = c.mamba
    h = mc.n_heads(c.d_model)
    bc = sds((1, seq, mc.n_groups, mc.d_state), f32)
    return ops.mamba2_ssd, (sds((1, seq, h, mc.head_dim), f32),
                            sds((1, seq, h), f32), sds((h,), f32), bc, bc,
                            sds((h,), f32))


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "wkv6", "mamba2_ssd"])
def test_kernel_compiles_for_v5e(one_chip, name):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _kernel_case(name, sds)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()     # the Mosaic kernel


def test_olmo_serve_step_compiles_and_fits_one_chip(one_chip):
    from repro.models import model as M
    from repro.models import transformer as T
    from repro.serve.decode import make_serve_step

    cfg = get_arch("olmo-1b")
    slots, buf = 8, 1024

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(functools.partial(M.init_params, cfg),
                                    jax.random.PRNGKey(0)))
    states = on_chip(jax.eval_shape(functools.partial(
        T.init_decode_state, cfg, slots, buf)))
    batch = on_chip({"tokens": jax.ShapeDtypeStruct((slots, 1), jnp.int32),
                     "cache_len": jax.ShapeDtypeStruct((slots,),
                                                       jnp.int32)})
    compiled = jax.jit(make_serve_step(cfg, buf)).lower(
        params, states, batch).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes)
    assert used < HBM_BYTES, used


def test_generation_step_writes_its_donated_cache_in_place(one_chip):
    """mistral-nemo-12b's decode step at the generation cell's shapes (8
    layers, 32 slots, a 2,048-token buffer, bfloat16 weights), with the
    states donated as ``serve`` donates them: the output cache is the
    input's buffer, and no cache-sized temporary is left (one layer's K
    cache is 134 MB)."""
    from repro.models import model as M
    from repro.models import transformer as T
    from repro.serve.decode import make_serve_step

    cfg = dataclasses.replace(get_arch("mistral-nemo-12b"), n_layers=8)
    slots, buf = 32, 2048

    def on_chip(tree, dtype=None):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, dtype or a.dtype, sharding=one_chip), tree)

    params = on_chip(jax.eval_shape(functools.partial(M.init_params, cfg),
                                    jax.random.PRNGKey(0)), jnp.bfloat16)
    states = on_chip(jax.eval_shape(functools.partial(
        T.init_decode_state, cfg, slots, buf)))
    batch = on_chip({"tokens": jax.ShapeDtypeStruct((slots, 1), jnp.int32),
                     "cache_len": jax.ShapeDtypeStruct((slots,),
                                                       jnp.int32)})
    compiled = jax.jit(make_serve_step(cfg, buf), donate_argnums=(1,)).lower(
        params, states, batch).compile()
    mem = compiled.memory_analysis()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(states))
    assert mem.alias_size_in_bytes == cache_bytes
    assert mem.temp_size_in_bytes < 64e6, mem.temp_size_in_bytes


@pytest.mark.parametrize("layout", ["fsdp", "resident"])
def test_sharded_olmo_decode_gathers_no_cache_on_2x2(topo, layout):
    """olmo-1b's decode step with its cache sharded by
    ``decode_state_specs`` over the 2x2 mesh, for one request, too few to
    shard: fsdp puts the KV heads on "model" and the sequence on "data",
    resident the sequence on data x model. No collective may rebuild any
    part of a cache, across the sequence or across the heads (GSPMD
    gathers by all-gather, or by an all-reduce of a padded buffer): none
    may return an array with the sequence extent of the buffer or of a
    shard of it."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import model as M
    from repro.models import transformer as T
    from repro.serve.decode import make_serve_step
    from repro.sharding import rules as SR

    cfg = get_arch("olmo-1b")
    batch_size, buf = 1, 12288
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    rules = SR.AxisRules.for_mesh(mesh)
    resident = layout == "resident"

    def named(specs):
        return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                            is_leaf=lambda x: isinstance(x, P))

    params = jax.eval_shape(functools.partial(M.init_params, cfg),
                            jax.random.PRNGKey(0))
    if resident:        # as the dry run serves it: bfloat16 weights
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16), params)
    states = jax.eval_shape(functools.partial(
        T.init_decode_state, cfg, batch_size, buf))
    batch = {"tokens": jax.ShapeDtypeStruct((batch_size, 1), jnp.int32),
             "cache_len": jax.ShapeDtypeStruct((batch_size,), jnp.int32)}
    SR.set_rules(rules)
    try:
        sspecs = SR.decode_state_specs(cfg, batch_size, rules, layout=layout)
        assert sspecs["layers"][0] == {
            "fsdp": P(None, None, "model", "data", None),
            "resident": P(None, None, None, ("data", "model"), None),
        }[layout]
        step = jax.jit(
            make_serve_step(cfg, buf),
            in_shardings=(named(SR.param_specs(cfg, rules, fsdp=not resident,
                                               param_shapes=params)),
                          named(sspecs),
                          named(SR.batch_specs(cfg, "decode", batch_size,
                                               rules, layout=layout))),
            out_shardings=(None, named(sspecs), None),
            donate_argnums=(1,))
        compiled = step.lower(params, states, batch).compile()
    finally:
        SR.set_rules(None)
    # a gathered piece of a cache carries the buffer's sequence extent or
    # a shard's; the buffer is chosen so that no weight has those extents
    seq = {buf, buf // 2, buf // 4}
    assert not seq & {d for a in jax.tree.leaves(params) for d in a.shape}
    collective = re.compile(r"= (.*?) (all-gather|all-reduce|all-to-all|"
                            r"collective-permute|reduce-scatter)"
                            r"(-start|-done)?\(")
    for line in compiled.as_text().splitlines():
        m = collective.search(line)
        if m:
            dims = {int(d) for shape in re.findall(r"\[([\d,]+)\]",
                                                   m.group(1))
                    for d in shape.split(",")}
            assert not dims & seq, line


def test_sharded_olmo_train_step_compiles_on_2x2(topo):
    from repro.launch.train import build_sharded_train
    from repro.models import model as M
    from repro.sharding import rules as SR
    from repro.train.optimizer import OptimizerConfig
    from repro.train.train_step import TrainConfig, make_opt_state

    cfg = dataclasses.replace(get_arch("olmo-1b"), n_layers=4)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    tcfg = TrainConfig()
    ocfg = OptimizerConfig(lr=3e-3, warmup_steps=5, total_steps=5)
    try:
        step, _, _ = build_sharded_train(cfg, tcfg, ocfg, mesh)
        params = jax.eval_shape(functools.partial(M.init_params, cfg),
                                jax.random.PRNGKey(0))
        opt = jax.eval_shape(functools.partial(make_opt_state, tcfg=tcfg),
                             params)
        tokens = jax.ShapeDtypeStruct((8, 1024), jnp.int32)
        compiled = step.lower(params, opt, {"tokens": tokens,
                                            "labels": tokens}).compile()
    finally:
        SR.set_rules(None)
    mem = compiled.memory_analysis()
    state_bytes = 16 * cfg.n_params()        # fp32 params + AdamW moments
    # FSDP over "data" and TP over "model": each chip holds well under
    # half of the state (the tied embedding shards over "model" only)
    assert mem.argument_size_in_bytes < state_bytes / 2
    assert "all-gather" in compiled.as_text()


def _moonlight(n_layers):
    """moonlight-16b-a3b at the MoE cell's share: 8 of 64 experts held,
    a vocabulary of 20,480, every width as published."""
    base = get_arch("moonlight-16b-a3b")
    return dataclasses.replace(base, n_layers=n_layers, vocab_size=20480,
                               moe=dataclasses.replace(base.moe, n_held=8))


def test_moe_cell_train_step_fits_one_chip(one_chip):
    """The MoE training cell's step (1 dense + 4 expert layers, B=2 x
    S=4096, parameters and AdamW state float32) compiles for one v5e with
    the grouped expert products as Mosaic kernels (forward, recomputed
    forward, and the backward's two products, for gate, up and down),
    and its arguments and temporaries fit the chip."""
    from repro.models import model as M
    from repro.train.optimizer import OptimizerConfig
    from repro.train.train_step import (TrainConfig, make_opt_state,
                                        make_train_step)

    cfg = _moonlight(5)
    tcfg = TrainConfig(remat="full")
    step = make_train_step(cfg, tcfg, OptimizerConfig(
        lr=3e-3, warmup_steps=5, total_steps=100, weight_decay=0.0))

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = jax.eval_shape(functools.partial(M.init_params, cfg),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(functools.partial(make_opt_state, tcfg=tcfg),
                         params)
    tokens = jax.ShapeDtypeStruct((2, 4096), jnp.int32, sharding=one_chip)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        on_chip(params), on_chip(opt),
        {"tokens": tokens, "labels": tokens}).compile()
    assert compiled.as_text().count("tpu_custom_call") == 12
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_expert_parallel_moe_step_compiles_on_2x2(topo):
    """moonlight's dense layer and one expert layer, trained over the 2x2
    mesh (FSDP over "data", the 8 held experts over "model"): routing,
    dispatch, the grouped products and the combine stay on each device
    (no collective under their scopes, no all-to-all), no device gathers
    the other's experts, and the experts' parts meet in the
    activation-sized psum."""
    import re

    from repro.launch.train import build_sharded_train
    from repro.models import model as M
    from repro.sharding import rules as SR
    from repro.train.optimizer import OptimizerConfig
    from repro.train.train_step import TrainConfig, make_opt_state

    cfg = _moonlight(2)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    tcfg = TrainConfig()
    ocfg = OptimizerConfig(lr=3e-3, warmup_steps=5, total_steps=5)
    batch, seq = 8, 1024
    try:
        step, _, _ = build_sharded_train(cfg, tcfg, ocfg, mesh)
        params = jax.eval_shape(functools.partial(M.init_params, cfg),
                                jax.random.PRNGKey(0))
        opt = jax.eval_shape(functools.partial(make_opt_state, tcfg=tcfg),
                             params)
        tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        compiled = step.lower(params, opt, {"tokens": tokens,
                                            "labels": tokens}).compile()
    finally:
        SR.set_rules(None)
    collective = re.compile(r"= (.*?) (all-gather|all-reduce|all-to-all|"
                            r"collective-permute|reduce-scatter)(-start)?\(")
    d, ff = cfg.d_model, cfg.moe.d_ff_expert
    activation = f"[{batch // 2},{seq},{d}]"
    reduced = []
    for line in compiled.as_text().splitlines():
        m = collective.search(line)
        if not m:
            continue
        assert m.group(2) != "all-to-all", line
        op = re.search(r'op_name="([^"]*)"', line)
        assert not (op and re.search(r"moe_(route|dispatch|experts|combine)",
                                     op.group(1))), line
        assert f"[8,{d},{ff}]" not in m.group(1), line
        assert f"[8,{ff},{d}]" not in m.group(1), line
        if m.group(2) == "all-reduce":
            reduced.append(m.group(1))
    assert any(activation in shape for shape in reduced), reduced
