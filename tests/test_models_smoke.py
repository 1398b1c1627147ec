"""Per-arch reduced-config smoke tests: one forward/train step on CPU,
asserting output shapes and no NaNs; plus decode-step state threading."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, list_archs
from repro.models import model as M
from repro.models import transformer as T

ARCHS = ["qwen3-32b", "qwen3-8b", "mistral-nemo-12b", "olmo-1b",
         "olmoe-1b-7b", "llama4-scout-17b-a16e", "rwkv6-7b",
         "llama-3.2-vision-11b", "zamba2-7b", "musicgen-large",
         "moonlight-16b-a3b"]


def _batch(cfg, b=2, s=32, key=0):
    k = jax.random.PRNGKey(key)
    if cfg.n_codebooks:
        tokens = jax.random.randint(k, (b, s, cfg.n_codebooks), 0,
                                    cfg.vocab_size)
    else:
        tokens = jax.random.randint(k, (b, s), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    return {"tokens": tokens, "labels": labels}


def _vision(cfg, b=2):
    if cfg.family != "vlm":
        return None
    return jax.random.normal(jax.random.PRNGKey(7),
                             (b, cfg.n_vision_tokens, cfg.vision_dim),
                             jnp.float32).astype(jnp.bfloat16)


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_and_loss(arch):
    cfg = get_arch(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    ctx = M.make_ctx(cfg, 32, "train", vision=_vision(cfg), remat="full")
    loss, metrics = M.loss_fn(params, batch, cfg, ctx)
    assert np.isfinite(float(loss)), f"{arch}: loss not finite"
    logits, aux, _ = M.forward(params, batch["tokens"], cfg, ctx)
    if cfg.n_codebooks:
        assert logits.shape == (2, 32, cfg.n_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (2, 32, cfg.vocab_size)
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())


@pytest.mark.parametrize("arch", ARCHS)
def test_grad_step(arch):
    cfg = get_arch(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    ctx = M.make_ctx(cfg, 32, "train", vision=_vision(cfg))

    def lf(p):
        return M.loss_fn(p, batch, cfg, ctx)[0]

    loss, grads = jax.value_and_grad(lf)(params)
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g, np.float32))) for g in flat)
    # at least some gradient is non-zero
    assert any(float(jnp.abs(g.astype(jnp.float32)).max()) > 0 for g in flat)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step(arch):
    cfg = get_arch(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    b, buf = 2, 16
    if cfg.mla:     # no latent K/V cache yet: refused before any trace
        with pytest.raises(ValueError, match="latent K/V cache"):
            T.init_decode_state(cfg, b, buf)
        return
    vision = _vision(cfg, b)
    states = T.init_decode_state(cfg, b, buf, vision=vision, params=params)
    cache_len = jnp.zeros((b,), jnp.int32)
    if cfg.n_codebooks:
        tok = jnp.ones((b, 1, cfg.n_codebooks), jnp.int32)
    else:
        tok = jnp.ones((b, 1), jnp.int32)
    for _ in range(3):
        ctx = M.make_ctx(cfg, buf, "decode", vision=vision,
                         cache_len=cache_len)
        logits, states = M.decode_step(params, tok, states, cache_len, cfg,
                                       ctx)
        cache_len = cache_len + 1
        assert not bool(jnp.isnan(logits.astype(jnp.float32)).any()), arch
    if cfg.n_codebooks:
        assert logits.shape == (b, 1, cfg.n_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (b, 1, cfg.vocab_size)


def test_all_archs_registered():
    assert set(ARCHS) <= set(list_archs())


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_positive(arch):
    cfg = get_arch(arch)
    n = cfg.n_params()
    na = cfg.n_active_params()
    assert n > 0 and na > 0 and na <= n


def _plain_causal_attention(q, k, v):
    """Causal softmax attention in float32, every score kept."""
    hi = jax.lax.Precision.HIGHEST
    s, d = q.shape[1], q.shape[-1]
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=hi) / np.sqrt(d)
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v,
                      precision=hi)


@pytest.mark.parametrize("s,dv", [(1024, 16), (1025, 16), (2048, 16),
                                  (2048, 8)])
def test_causal_attention_picks_its_core_by_length(s, dv):
    """Full attention up to 1024 positions, query chunks above (a length
    the chunk does not divide included); a v head narrower than q's and
    k's, as latent attention has. Output and gradients match the plain
    float32 reference."""
    from repro.models import blocks as B

    keys = jax.random.split(jax.random.PRNGKey(s + dv), 4)
    q, k = (jax.random.normal(kk, (1, s, 2, 16)) for kk in keys[:2])
    v = jax.random.normal(keys[2], (1, s, 2, dv))
    ct = jax.random.normal(keys[3], (1, s, 2, dv))
    out = B.causal_attention(q, k, v)
    core = (B.full_causal_attention if s <= 1024
            else B.chunked_causal_attention)
    np.testing.assert_array_equal(out, core(q, k, v))
    np.testing.assert_allclose(out, _plain_causal_attention(q, k, v),
                               atol=2e-5, rtol=2e-5)

    def grads(f):
        return jax.grad(lambda *a: jnp.sum(f(*a) * ct), (0, 1, 2))(q, k, v)
    for a, b in zip(grads(B.causal_attention),
                    grads(_plain_causal_attention)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
