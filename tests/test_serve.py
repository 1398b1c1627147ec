"""Serving correctness: the decode path (KV cache / SSM state threading)
must produce the same next-token logits as the parallel forward path —
teacher-forcing parity, the strongest cache-machinery test — and the
``serve`` loop, whose step writes new K/V rows into a donated cache, must
give every request what a forward pass over its own tokens gives."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch
from repro.models import model as M
from repro.models import transformer as T

PARITY_ARCHS = ["olmo-1b", "qwen3-8b", "rwkv6-7b", "zamba2-7b",
                "musicgen-large", "llama-3.2-vision-11b",
                "olmoe-1b-7b", "llama4-scout-17b-a16e"]


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_decode_matches_parallel_forward(arch):
    # the MoE archs run the dropless dispatch: a token's experts are the
    # same whether it is routed with its whole sequence or alone
    cfg = get_arch(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 12
    key = jax.random.PRNGKey(1)
    if cfg.n_codebooks:
        tokens = jax.random.randint(key, (b, s, cfg.n_codebooks), 0,
                                    cfg.vocab_size)
    else:
        tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    vision = None
    if cfg.family == "vlm":
        vision = jax.random.normal(
            jax.random.PRNGKey(2), (b, cfg.n_vision_tokens,
                                    cfg.vision_dim)).astype(jnp.bfloat16)

    # parallel forward (fp32 compute for a tight reference)
    ctx = M.make_ctx(cfg, s, "train", vision=vision, remat=None,
                     compute_dtype=jnp.float32)
    ref_logits, _, _ = M.forward(params, tokens, cfg, ctx)

    # decode path, token by token
    states = T.init_decode_state(cfg, b, s, dtype=jnp.float32,
                                 vision=vision, params=params)
    cache_len = jnp.zeros((b,), jnp.int32)
    outs = []
    for t in range(s):
        tok = tokens[:, t:t + 1]
        dctx = M.make_ctx(cfg, s, "decode", vision=vision,
                          cache_len=cache_len,
                          compute_dtype=jnp.float32)
        logits, states = M.decode_step(params, tok, states, cache_len,
                                       cfg, dctx)
        outs.append(logits)
        cache_len = cache_len + 1
    dec_logits = jnp.concatenate(outs, axis=1)

    np.testing.assert_allclose(
        np.asarray(dec_logits, np.float32),
        np.asarray(ref_logits, np.float32), rtol=2e-3, atol=2e-3)


def test_greedy_generate_shapes():
    from repro.serve.decode import greedy_generate
    cfg = get_arch("olmo-1b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0,
                                cfg.vocab_size)
    out = greedy_generate(cfg, params, prompt, max_new=4)
    assert out.shape == (2, 4)
    assert bool((out >= 0).all()) and bool((out < cfg.vocab_size).all())


# the serving loop reuses slots: a finished slot restarts at cache_len 0
# over its previous request's rows, and once the queue is empty the slots
# left idle keep stepping while the longest request finishes
SERVE_CASES = {
    "refill_over_stale_rows": dict(slots=2, lengths=[9, 2, 5, 3, 7]),
    "idle_slots": dict(slots=3, lengths=[10, 2, 3, 2]),
}


@pytest.mark.parametrize("arch", ["olmo-1b", "qwen3-8b"])
@pytest.mark.parametrize("case", sorted(SERVE_CASES))
def test_serve_gives_each_request_its_forward_tokens(arch, case):
    from repro.launch.serve import serve
    cfg = get_arch(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    spec = SERVE_CASES[case]
    prompts = [rng.integers(0, cfg.vocab_size, n).tolist()
               for n in spec["lengths"]]
    max_new = 3
    res = serve(cfg, params, prompts, slots=spec["slots"], buffer_len=13,
                max_new=max_new)
    for r, prompt in enumerate(prompts):
        seq = jnp.asarray([prompt + res.produced[r][:-1]])
        ctx = M.make_ctx(cfg, seq.shape[1], "train", remat=None,
                         compute_dtype=jnp.float32)
        logits, _, _ = M.forward(params, seq, cfg, ctx)
        want = np.asarray(logits[0, len(prompt) - 1:], np.float32)
        scale = np.abs(want).max()
        # serve computes in bfloat16: its first token's logits within 3 %
        # of the logit scale, and each token it picked within 6 % of the
        # best (a near tie may flip); attending to a stale row moves
        # them by tens of percent
        np.testing.assert_allclose(res.prompt_logits[r], want[0],
                                   atol=0.03 * scale, rtol=0)
        tok = np.asarray(res.produced[r])
        gap = want.max(-1) - want[np.arange(max_new), tok]
        assert gap.max() <= 0.06 * scale, (r, gap, scale)


@pytest.mark.parametrize("arch", ["olmo-1b", "zamba2-7b"])
def test_decode_write_past_buffer_is_dropped(arch):
    """A slot whose position has run past the buffer writes nothing and
    leaves the other slots' results as they are. (``serve`` cannot get
    there: it refuses requests longer than the buffer.)"""
    from repro.serve.decode import make_serve_step
    cfg = get_arch(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    buf = 8
    step = jax.jit(make_serve_step(cfg, buf), donate_argnums=(1,))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 1), 0,
                                cfg.vocab_size)

    def run(cache_len):
        # a random cache, so that a write shows wherever it lands
        states = jax.tree.map(
            lambda a: jax.random.normal(jax.random.PRNGKey(2), a.shape,
                                        a.dtype),
            T.init_decode_state(cfg, 3, buf))
        before = jax.tree.map(np.asarray, states)
        logits, after, _ = step(params, states,
                                {"tokens": tokens,
                                 "cache_len": jnp.asarray(cache_len,
                                                          jnp.int32)})
        return np.asarray(logits, np.float32), before, \
            jax.tree.map(np.asarray, after)

    logits, before, after = run([3, buf, buf + 5])
    logits_in, _, _ = run([3, 0, 0])
    np.testing.assert_array_equal(logits[0], logits_in[0])
    # the attention caches: every layer's (dense), or the shared block's
    key = "layers" if T.build_layout(cfg)["kind"] == "uniform" else "single"
    for old, cur in zip(before[key], after[key]):
        # (*stack, B, KV, S, D): slot 0 gains its row at position 3,
        # slots 1 and 2 are past the buffer and keep every row
        changed = (old != cur).any(axis=-1)
        want = np.zeros(changed.shape, bool)
        want[..., 0, :, 3] = True
        np.testing.assert_array_equal(changed, want)
