"""Serving steps: prefill and one-token decode."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import model as M
from repro.models import transformer as T


def make_prefill_step(cfg: ArchConfig, *, compute_dtype=jnp.bfloat16):
    def prefill_step(params, batch):
        tokens = batch["tokens"]
        ctx = M.make_ctx(cfg, tokens.shape[1], "prefill", remat=None,
                         vision=batch.get("vision"),
                         compute_dtype=compute_dtype)
        return M.prefill(params, tokens, cfg, ctx)

    return prefill_step


def make_serve_step(cfg: ArchConfig, buffer_len: int, *,
                    compute_dtype=jnp.bfloat16):
    """One new token against a KV cache / SSM state of ``buffer_len``."""

    def serve_step(params, states, batch):
        tokens = batch["tokens"]          # (B, 1[, K])
        cache_len = batch["cache_len"]    # (B,) current filled length
        ctx = M.make_ctx(cfg, buffer_len, "decode",
                         vision=batch.get("vision"), cache_len=cache_len,
                         compute_dtype=compute_dtype)
        logits, new_states = M.decode_step(params, tokens, states,
                                           cache_len, cfg, ctx)
        next_tok = jnp.argmax(logits[:, -1], axis=-1)
        return logits, new_states, next_tok

    return serve_step


def greedy_generate(cfg: ArchConfig, params, prompt, max_new: int,
                    vision=None):
    """Reference autoregressive loop (tiny models / examples): prefill the
    prompt token-by-token through the decode path, then generate."""
    b = prompt.shape[0]
    buf = prompt.shape[1] + max_new
    states = T.init_decode_state(cfg, b, buf, vision=vision, params=params)
    cache_len = jnp.zeros((b,), jnp.int32)
    step = jax.jit(make_serve_step(cfg, buf), donate_argnums=(1,))
    toks = prompt
    out = []
    cur = toks[:, :1]
    for i in range(buf - 1):
        batch = {"tokens": cur, "cache_len": cache_len}
        if vision is not None:
            batch["vision"] = vision
        logits, states, nxt = step(params, states, batch)
        cache_len = cache_len + 1
        if i + 1 < prompt.shape[1]:
            cur = toks[:, i + 1:i + 2]            # teacher-force the prompt
        else:
            cur = nxt[:, None] if nxt.ndim == 1 else nxt[:, None, :]
            out.append(cur)
    return jnp.concatenate(out, axis=1) if out else prompt[:, :0]
