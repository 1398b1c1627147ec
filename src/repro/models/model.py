"""LM wrapper: embedding, block stack, head, loss, prefill/decode entries."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import blocks as B
from repro.models import transformer as T
from repro.sharding import constrain


def init_params(cfg: ArchConfig, key):
    k1, k2, k3 = jax.random.split(key, 3)
    d = cfg.d_model
    if cfg.n_codebooks:
        embed = jax.random.normal(k1, (cfg.n_codebooks, cfg.vocab_size, d),
                                  jnp.float32) * 0.02
    else:
        embed = jax.random.normal(k1, (cfg.vocab_size, d), jnp.float32) * 0.02
    params = {"embed": embed, "final_norm": B.init_norm(cfg)}
    params.update(T.init_stack(cfg, k2))
    if not cfg.tie_embeddings:
        if cfg.n_codebooks:
            params["lm_head"] = B._dense_init(
                k3, (d, cfg.n_codebooks * cfg.vocab_size), fan_in=d)
        else:
            params["lm_head"] = B._dense_init(k3, (d, cfg.vocab_size),
                                              fan_in=d)
    return params


def make_ctx(cfg: ArchConfig, seq_len: int, mode: str, *,
             remat: Optional[str] = "full", vision=None, cache_len=None,
             compute_dtype=jnp.bfloat16) -> dict:
    ctx = {"mode": mode, "remat": remat, "compute_dtype": compute_dtype}
    if not cfg.attention_free:
        ctx["rope"] = B.rope_table(seq_len, cfg.rope_dim, cfg.rope_theta)
    if vision is not None:
        ctx["vision"] = vision
    if cache_len is not None:
        ctx["cache_len"] = cache_len
        ctx["positions"] = cache_len[:, None]
    return ctx


def embed_tokens(params, tokens, cfg: ArchConfig, compute_dtype):
    if cfg.n_codebooks:
        # tokens (B, S, K) -> sum_k embed[k][tokens[..., k]]
        return jnp.einsum("bskv,kvd->bsd",
                          jax.nn.one_hot(tokens, cfg.vocab_size,
                                         dtype=compute_dtype),
                          params["embed"].astype(compute_dtype))
    return jnp.take(params["embed"], tokens, axis=0).astype(compute_dtype)


def lm_logits(params, x, cfg: ArchConfig):
    with jax.named_scope("lm_head"):
        xf = B.apply_norm(params["final_norm"], x, cfg)
        if cfg.tie_embeddings:
            w = params["embed"].T
        else:
            w = params["lm_head"]
        logits = xf @ w.astype(xf.dtype)
        if cfg.n_codebooks:
            b, s, _ = logits.shape
            logits = logits.reshape(b, s, cfg.n_codebooks, cfg.vocab_size)
        return logits


def forward(params, tokens, cfg: ArchConfig, ctx: dict, states=None):
    """Returns (logits, aux, new_states)."""
    cd = ctx.get("compute_dtype", jnp.bfloat16)
    x = embed_tokens(params, tokens, cfg, cd)
    x = constrain(x, ("batch", None, None))
    x, aux, new_states = T.apply_stack(params, x, cfg, ctx, states)
    logits = lm_logits(params, x, cfg)
    return logits, aux, new_states


def loss_fn(params, batch, cfg: ArchConfig, ctx: dict):
    """Next-token CE. batch: tokens (B,S[,K]) + labels (B,S[,K]),
    labels[t] = target for position t (-100 = ignore)."""
    logits, aux, _ = forward(params, batch["tokens"], cfg, ctx)
    labels = batch["labels"]
    logits = logits.astype(jnp.float32)
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    ntok = jnp.maximum(valid.sum(), 1)
    loss = nll.sum() / ntok
    metrics = dict(aux, loss=loss, ntokens=ntok)
    return loss + aux["aux_loss"], metrics


def prefill(params, tokens, cfg: ArchConfig, ctx: dict):
    """Forward over the prompt; returns last-position logits.

    (Cache export for chained decode lives in serve/decode.py; the
    prefill step is logits-only, which matches a scoring/prefill step.)"""
    logits, aux, _ = forward(params, tokens, cfg, ctx)
    return logits[:, -1]


def decode_step(params, tokens, states, cache_len, cfg: ArchConfig,
                ctx: dict):
    """One-token decode. tokens (B,1[,K]); states from init_decode_state.
    Returns (logits (B,1[,K],V), new_states)."""
    logits, _, new_states = forward(params, tokens, cfg, ctx, states)
    return logits, new_states
