"""Sharding rules: spec trees match param trees structurally for every
arch, every sharded dim divides evenly on the production meshes, and the
decode-state/batch specs are coherent (property-style sweep over all 10
archs x both meshes via AbstractMesh — no device initialization)."""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_arch, list_archs
from repro.configs.shapes import SHAPES, applicable
from repro.models import model as M
from repro.models import transformer as T
from repro.sharding import make_abstract_mesh
from repro.sharding import rules as SR

MESHES = {
    "single": make_abstract_mesh((16, 16), ("data", "model")),
    "multi": make_abstract_mesh((2, 16, 16), ("pod", "data", "model")),
}


def _rules(mesh_name):
    return SR.AxisRules.for_mesh(MESHES[mesh_name])


def _param_shapes(arch):
    cfg = get_arch(arch)
    return cfg, jax.eval_shape(functools.partial(M.init_params, cfg),
                               jax.random.PRNGKey(0))


def _axis_size(mesh, entry):
    names = entry if isinstance(entry, tuple) else (entry,)
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("mesh_name", ["single", "multi"])
def test_param_specs_match_and_divide(arch, mesh_name):
    cfg, shapes = _param_shapes(arch)
    rules = _rules(mesh_name)
    specs = SR.param_specs(cfg, rules, fsdp=True, param_shapes=shapes)
    mesh = MESHES[mesh_name]

    # structural match: tree.map succeeds leaf-for-leaf
    def check(sds, spec):
        assert isinstance(spec, P), spec
        assert len(spec) <= len(sds.shape), (sds.shape, spec)
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            size = _axis_size(mesh, entry)
            assert sds.shape[dim] % size == 0, \
                (arch, sds.shape, spec, dim)
        return 0

    jax.tree.map(check, shapes, specs,
                 is_leaf=lambda x: isinstance(x, P) or hasattr(x, "shape"))


@pytest.mark.parametrize("arch", list_archs())
def test_opt_state_specs_cover_params(arch):
    from repro.train.optimizer import opt_state_specs
    cfg, shapes = _param_shapes(arch)
    rules = _rules("single")
    pspecs = SR.param_specs(cfg, rules, fsdp=True, param_shapes=shapes)
    ospecs = opt_state_specs(pspecs, shapes, rules)
    assert set(ospecs) == {"mu", "nu", "step"}
    # moments shaped like params
    jax.tree.map(lambda a, b: None, pspecs, ospecs["mu"],
                 is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("shape_name", ["decode_32k", "long_500k"])
@pytest.mark.parametrize("layout", ["fsdp", "resident"])
def test_decode_state_specs_match(arch, shape_name, layout):
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    ok, _ = applicable(cfg, shape)
    if not ok:
        pytest.skip("n/a cell")
    rules = _rules("single")
    SR.set_rules(None)
    init = functools.partial(T.init_decode_state, cfg, shape.global_batch,
                             shape.seq_len)
    if cfg.family == "vlm":
        # the cross-attention K/V are computed from the vision input
        _, params = _param_shapes(arch)
        vision = jax.ShapeDtypeStruct(
            (shape.global_batch, cfg.n_vision_tokens, cfg.vision_dim),
            jnp.bfloat16)
        state_shapes = jax.eval_shape(
            lambda v, pp: init(vision=v, params=pp), vision, params)
    else:
        state_shapes = jax.eval_shape(init)
    specs = SR.decode_state_specs(cfg, shape.global_batch, rules,
                                  layout=layout)
    mesh = MESHES["single"]

    def check(sds, spec):
        assert len(spec) <= len(sds.shape)
        for dim, entry in enumerate(spec):
            if entry is None:
                continue
            assert sds.shape[dim] % _axis_size(mesh, entry) == 0, \
                (arch, shape_name, layout, sds.shape, spec)

    jax.tree.map(check, state_shapes, specs,
                 is_leaf=lambda x: isinstance(x, P) or hasattr(x, "shape"))
    lay = T.build_layout(cfg)
    if lay["kind"] == "uniform" and lay["block"] in T.ATTN_BLOCKS:
        # caches are (L, B, KV, S, D): KV heads take only the model axis,
        # the resident layout shards the sequence over data x model
        k_shape, k_spec = state_shapes["layers"][0].shape, specs["layers"][0]
        assert k_shape[-2] == shape.seq_len
        assert k_spec[-3] in (None, "model"), k_spec
        if layout == "resident":
            assert k_spec[-2] == ("data", "model"), k_spec


@pytest.mark.parametrize("gb,expected_sharded", [(256, True), (1, False)])
def test_batch_specs_small_batch_fallback(gb, expected_sharded):
    cfg = get_arch("qwen3-8b")
    rules = _rules("single")
    specs = SR.batch_specs(cfg, "train", gb, rules)
    sharded = specs["tokens"][0] is not None
    assert sharded == expected_sharded


def test_constrain_noop_without_rules():
    SR.set_rules(None)
    x = jnp.ones((4, 4))
    assert SR.constrain(x, ("batch", None)) is x
