"""Plain reference of the dense decoder that the cells run.

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``, with
no cache, no batching of requests and no kernels; it imports nothing of
the program. Weights are drawn from the seed exactly as the program's
``models/model.py`` ``init_params`` draws them (same keys, shapes and
order), so both sides hold the same numbers without one taking them from
the other.

``quant="fp8"`` rounds both operands of every matrix product to
float8_e4m3 first: the control, one precision step below the bfloat16
compute that the configurations state.

The layer equations (OLMo-1B, arXiv:2402.00838; Mistral-NeMo, as the
Mistral 7B paper arXiv:2310.06825 with grouped KV heads and RoPE base
1e6): pre-norm residual blocks of causal multi-head attention with
rotary position embedding (rotate-half form) and a SwiGLU MLP; OLMo's
norms are LayerNorm without scale or bias, Mistral's are RMSNorm with a
scale; OLMo ties the head to the embedding.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _round(x, quant):
    if quant == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def mm(eq, a, b, quant=None):
    return jnp.einsum(eq, _round(a, quant), _round(b, quant), precision=HI,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# weights, drawn as the program draws them
# ---------------------------------------------------------------------------

def _dense(key, shape, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(fan_in)


def _norm_params(cfg):
    if not cfg["parametric_norm"]:
        return {"_np": jnp.zeros((0,), jnp.float32)}
    if cfg["norm_type"] == "layernorm":
        return {"scale": jnp.ones((cfg["d_model"],), jnp.float32),
                "bias": jnp.zeros((cfg["d_model"],), jnp.float32)}
    return {"scale": jnp.ones((cfg["d_model"],), jnp.float32)}


def head_dim(cfg) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def init_layer(cfg, key):
    d, hd = cfg["d_model"], head_dim(cfg)
    h, kv, ff = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_ff"]
    ka, km, _ = jax.random.split(key, 3)
    a1, a2, a3, a4 = jax.random.split(ka, 4)
    m1, m2, m3 = jax.random.split(km, 3)
    return {"attn": {"wq": _dense(a1, (d, h * hd)),
                     "wk": _dense(a2, (d, kv * hd)),
                     "wv": _dense(a3, (d, kv * hd)),
                     "wo": _dense(a4, (h * hd, d), fan_in=h * hd)},
            "mlp": {"w_gate": _dense(m1, (d, ff)),
                    "w_up": _dense(m2, (d, ff)),
                    "w_down": _dense(m3, (ff, d), fan_in=ff)},
            "ln1": _norm_params(cfg), "ln2": _norm_params(cfg)}


def _keys(cfg, key):
    k1, k2, k3 = jax.random.split(key, 3)
    return k1, jax.random.split(k2, cfg["n_layers"]), k3


def outer_weights_key(cfg, key):
    """Embedding, final norm and (untied) head."""
    k1, _, k3 = _keys(cfg, key)
    out = {"embed": jax.random.normal(
        k1, (cfg["vocab_size"], cfg["d_model"]), jnp.float32) * 0.02,
        "final_norm": _norm_params(cfg)}
    if not cfg["tie_embeddings"]:
        out["lm_head"] = _dense(k3, (cfg["d_model"], cfg["vocab_size"]))
    return out


def init_params_key(cfg, key):
    """The whole tree, in the program's layout (layers stacked)."""
    params = outer_weights_key(cfg, key)
    params["layers"] = jax.vmap(lambda k: init_layer(cfg, k))(
        _keys(cfg, key)[1])
    return params


def _items(cfg) -> tuple:
    return tuple(sorted(cfg.items()))


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _layer_w(cfg_items, key, layer, bf16):
    w = init_layer(dict(cfg_items), _keys(dict(cfg_items), key)[1][layer])
    return _maybe_bf16(w, bf16)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _outer_w(cfg_items, key, bf16):
    return _maybe_bf16(outer_weights_key(dict(cfg_items), key), bf16)


@functools.partial(jax.jit, static_argnums=(0,))
def _all_w(cfg_items, key):
    return init_params_key(dict(cfg_items), key)


def _maybe_bf16(tree, bf16):
    if not bf16:
        return tree
    return jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32),
                        tree)


def init_params(cfg, seed):
    """The whole tree from the seed, float32, as the program draws it."""
    return _all_w(_items(cfg), jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def norm(p, x, cfg):
    eps = cfg["norm_eps"]
    if cfg["norm_type"] == "layernorm" or not cfg["parametric_norm"]:
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + eps)
        if cfg["parametric_norm"]:
            y = y * p["scale"] + p["bias"]
        return y
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y * p["scale"]


def rope(x, positions, theta):
    """Rotate-half RoPE; x (B, S, H, D), positions (S,)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer(p, x, cfg, quant=None):
    """One decoder layer over a whole causal sequence; x (B, S, d)."""
    b, s, _ = x.shape
    h, kv, hd = cfg["n_heads"], cfg["n_kv_heads"], head_dim(cfg)
    pos = jnp.arange(s)
    a = p["attn"]
    y = norm(p["ln1"], x, cfg)
    q = mm("bsd,de->bse", y, a["wq"], quant).reshape(b, s, h, hd)
    k = mm("bsd,de->bse", y, a["wk"], quant).reshape(b, s, kv, hd)
    v = mm("bsd,de->bse", y, a["wv"], quant).reshape(b, s, kv, hd)
    q = rope(q, pos, cfg["rope_theta"])
    k = rope(k, pos, cfg["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)        # head i reads kv head i // g
    v = jnp.repeat(v, h // kv, axis=2)
    sc = mm("bqhd,bkhd->bhqk", q, k, quant) * hd ** -0.5
    mask = pos[:, None] >= pos[None, :]
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = mm("bhqk,bkhd->bqhd", pr, v, quant).reshape(b, s, h * hd)
    x = x + mm("bse,ed->bsd", o, a["wo"], quant)
    y = norm(p["ln2"], x, cfg)
    m = p["mlp"]
    g = jax.nn.silu(mm("bsd,df->bsf", y, m["w_gate"], quant))
    u = mm("bsd,df->bsf", y, m["w_up"], quant)
    return x + mm("bsf,fd->bsd", g * u, m["w_down"], quant)


def logits(outer, x, cfg, quant=None):
    xf = norm(outer["final_norm"], x, cfg)
    if cfg["tie_embeddings"]:
        return mm("...d,vd->...v", xf, outer["embed"], quant)
    return mm("...d,dv->...v", xf, outer["lm_head"], quant)


# ---------------------------------------------------------------------------
# training: three AdamW steps, as the program's optimizer states them
# ---------------------------------------------------------------------------

def _row_nll_sum(params, tokens, labels, cfg, quant):
    x = jnp.take(params["embed"], tokens, axis=0)
    for i in range(cfg["n_layers"]):
        x = layer(jax.tree.map(lambda a: a[i], params["layers"]), x, cfg,
                  quant)
    lg = logits(params, x, cfg, quant)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(logz - gold)


@functools.partial(jax.jit, static_argnums=(4, 5), donate_argnums=(1,))
def _accumulate(params, acc, tokens, labels, cfg_items, quant):
    cfg = dict(cfg_items)
    nll, g = jax.value_and_grad(_row_nll_sum)(params, tokens, labels, cfg,
                                              quant)
    return jax.tree.map(jnp.add, acc, g), nll


def schedule(step, lr, warmup, total, min_lr_frac=0.1):
    step = float(step)
    warm = min(step / max(warmup, 1), 1.0)
    prog = min(max((step - warmup) / max(total - warmup, 1), 0.0), 1.0)
    frac = min_lr_frac + (1 - min_lr_frac) * 0.5 * (1 + math.cos(math.pi
                                                                  * prog))
    return lr * warm * frac


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw(params, grads, mu, nu, step, lr):
    b1, b2, eps, clip = 0.9, 0.95, 1e-8, 1.0
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    g = jax.tree.map(lambda x: x * scale, grads)
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda n, x: b2 * n + (1 - b2) * jnp.square(x), nu, g)
    params = jax.tree.map(
        lambda p, m, n: p - lr * ((m / bc1) / (jnp.sqrt(n / bc2) + eps)),
        params, mu, nu)
    return params, mu, nu, g


def train_steps(cfg, seed, batches, *, lr, warmup, total, quant=None,
                rows_per_block=1):
    """Run ``len(batches)`` AdamW steps of the reference from the seed's
    weights (weight decay 0, clip 1, betas 0.9/0.95, eps 1e-8, cosine
    to a tenth after ``warmup`` linear steps over ``total``).

    Returns per-step losses, the per-leaf norms of the first clipped
    gradient, and per-leaf norms of the parameters' change over all steps,
    each as a flat {leaf path: float}."""
    cfg_items = _items(cfg)
    params = init_params(cfg, seed)
    start = jax.tree.map(jnp.copy, params)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for i, batch in enumerate(batches):
        acc = jax.tree.map(jnp.zeros_like, params)
        total_nll = 0.0
        tokens, labels = batch["tokens"], batch["labels"]
        ntok = tokens.size
        for r in range(0, tokens.shape[0], rows_per_block):
            acc, nll = _accumulate(params, acc,
                                   jnp.asarray(tokens[r:r + rows_per_block]),
                                   jnp.asarray(labels[r:r + rows_per_block]),
                                   cfg_items, quant)
            total_nll += float(nll)
        grads = jax.tree.map(lambda a: a / ntok, acc)
        losses.append(total_nll / ntok)
        step = i + 1
        params, mu, nu, g = _adamw(
            params, grads, mu, nu, jnp.float32(step),
            jnp.float32(schedule(step, lr, warmup, total)))
        if first_grad is None:
            first_grad = leaf_norms(g)
        del g, grads
    change = leaf_norms(jax.tree.map(jnp.subtract, params, start))
    return losses, first_grad, change


def leaf_paths(tree) -> list[str]:
    return ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def leaf_norms(tree) -> dict[str, float]:
    """{leaf path: L2 norm}; stacked layers count as one leaf each."""
    return dict(zip(leaf_paths(tree), (float(v) for v in _norms(tree))))


# ---------------------------------------------------------------------------
# serving: logits at chosen positions, layer by layer
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_jit(p, x, cfg_items, quant):
    return layer(p, x, dict(cfg_items), quant)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _logits_at(outer, x, pos, cfg_items, quant):
    return logits(outer, jnp.take(x, pos, axis=0), dict(cfg_items), quant)


def served_logits(cfg, seed, seqs, positions, pad_to, *, quant=None):
    """Logits (len(pos), vocab) at ``positions[i]`` of each sequence
    ``seqs[i]`` (padded on the right to ``pad_to``; causal, so padding
    changes nothing before it). Weights are drawn layer by layer and
    rounded to bfloat16, the dtype they are served in, then computed in
    float32. Every ``positions[i]`` has the same length."""
    cfg_items = _items(cfg)
    key = jax.random.PRNGKey(seed)
    outer = _outer_w(cfg_items, key, True)
    xs = []
    for s in seqs:
        toks = jnp.zeros((1, pad_to), jnp.int32).at[0, :len(s)].set(
            jnp.asarray(s, jnp.int32))
        xs.append(jnp.take(outer["embed"], toks, axis=0))
    for li in range(cfg["n_layers"]):
        p = _layer_w(cfg_items, key, li, True)
        xs = [_layer_jit(p, x, cfg_items, quant) for x in xs]
        del p
    return [_logits_at(outer, x[0], jnp.asarray(pos, jnp.int32), cfg_items,
                       quant) for x, pos in zip(xs, positions)]
