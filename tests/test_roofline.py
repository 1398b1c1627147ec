"""HLO cost model: while-trip accounting, collective parsing, dot FLOPs —
validated against programs with known costs (and documenting the XLA
cost_analysis undercount that motivated the custom model)."""
import jax
import jax.numpy as jnp
import pytest

from repro.roofline import analysis as RA
from repro.roofline.hlo_cost import module_cost, parse_module


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_flops_multiplied_by_trip_count():
    n, trips = 128, 10

    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        return jax.lax.scan(body, x, w)[0]

    x = jax.ShapeDtypeStruct((n, n), jnp.float32)
    w = jax.ShapeDtypeStruct((trips, n, n), jnp.float32)
    c = _compile(f, x, w)
    cost = module_cost(c.as_text())
    expected = 2 * n ** 3 * trips
    assert expected <= cost.flops <= expected * 1.1
    # the motivating bug: XLA's own analysis counts the body ONCE
    ca = c.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    assert ca["flops"] < expected / (trips - 1)


def test_plain_dot_flops():
    a = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 32), jnp.float32)
    c = _compile(lambda a, b: a @ b, a, b)
    cost = module_cost(c.as_text())
    want = 2 * 64 * 256 * 32
    assert want <= cost.flops <= want * 1.05
    # bytes: operands + result at minimum
    assert cost.bytes >= (64 * 256 + 256 * 32 + 64 * 32) * 4


def test_nested_scan_flops():
    n, inner, outer = 64, 3, 5

    def f(x, w):
        def outer_body(c, wo):
            def inner_body(ci, wi):
                return jnp.tanh(ci @ wi), None
            return jax.lax.scan(inner_body, c, wo)[0], None
        return jax.lax.scan(outer_body, x, w)[0]

    x = jax.ShapeDtypeStruct((n, n), jnp.float32)
    w = jax.ShapeDtypeStruct((outer, inner, n, n), jnp.float32)
    cost = module_cost(_compile(f, x, w).as_text())
    want = 2 * n ** 3 * inner * outer
    assert want <= cost.flops <= want * 1.2


def test_parse_module_structure():
    c = _compile(lambda x: jnp.sum(x * 2), jax.ShapeDtypeStruct((32,),
                                                                jnp.float32))
    comps = parse_module(c.as_text())
    assert any(len(comp.instrs) > 0 for comp in comps.values())


def test_roofline_terms_and_dominant():
    r = RA.Roofline(flops_per_device=197e12, bytes_per_device=819e9 * 2,
                    collective_bytes=50e9 * 0.5,
                    collectives=RA.CollectiveStats({}, {}),
                    model_flops=197e12 * 128, n_chips=256)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(2.0)
    assert r.collective_s == pytest.approx(0.5)
    assert r.dominant == "memory"
    assert r.step_time_s == pytest.approx(2.0)
    assert r.useful_flops_ratio == pytest.approx(0.5)
    assert r.roofline_fraction == pytest.approx(128 / (256 * 2.0))


def test_model_flops_kinds():
    from repro.configs.base import get_arch
    from repro.configs.shapes import SHAPES
    cfg = get_arch("qwen3-8b")
    t = RA.model_flops(cfg, SHAPES["train_4k"])
    p = RA.model_flops(cfg, SHAPES["prefill_32k"])
    d = RA.model_flops(cfg, SHAPES["decode_32k"])
    assert t == 6.0 * cfg.n_active_params() * 256 * 4096
    assert p == 2.0 * cfg.n_active_params() * 32 * 32768
    assert d < p  # one token vs a full prompt
    # MoE: active < total reflected in model flops
    moe = get_arch("olmoe-1b-7b")
    assert moe.n_active_params() < moe.n_params()


def test_collective_parse_sharded_program():
    # needs >1 device: use a 1-device mesh psum via shard_map (no comm) —
    # just assert the parser doesn't crash and reports zero collectives
    c = _compile(lambda x: x + 1, jax.ShapeDtypeStruct((8,), jnp.float32))
    cost = module_cost(c.as_text())
    assert cost.coll_bytes == 0


def test_device_peaks_keyed_by_device_kind():
    from repro.roofline.analysis import device_peaks
    v5e = device_peaks("TPU v5 lite")
    assert (v5e.peak_flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        device_peaks("TPU v0 unknown")


def test_tuning_family_is_interpret_on_cpu_and_unknown_raises():
    from repro.core.provision import autotune as AT
    assert AT.default_family() == "interpret"
    assert AT._family_hw("interpret") is AT.INTERPRET_HW
    assert AT._family_hw("TPU v5 lite").peak_flops == 197e12
    with pytest.raises(KeyError):
        AT._family_hw("tpu")
