"""Core transformer blocks: norms, RoPE, GQA attention (chunked online-softmax
XLA path + pluggable Pallas path), latent attention (MLA), SwiGLU MLP, and
a dropless MoE whose expert products are one grouped matrix product.

All blocks are pure functions over param pytrees (dicts of jnp arrays).
Params live in fp32; forward casts to ``compute_dtype`` at block entry.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig

# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def _dense_init(key, shape, fan_in=None, dtype=jnp.float32):
    fan_in = fan_in if fan_in is not None else shape[0]
    return (jax.random.normal(key, shape, dtype) / jnp.sqrt(fan_in)).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ArchConfig, key=None):
    if not cfg.parametric_norm:
        return {"_np": jnp.zeros((0,), jnp.float32)}  # non-parametric sentinel
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                "bias": jnp.zeros((cfg.d_model,), jnp.float32)}
    return {"scale": jnp.ones((cfg.d_model,), jnp.float32)}


def apply_norm(p, x, cfg: ArchConfig):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    if cfg.norm_type == "layernorm" or not cfg.parametric_norm:
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        if cfg.parametric_norm and "scale" in p:
            y = y * p["scale"] + p["bias"]
    else:
        y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + cfg.norm_eps)
        y = y * p["scale"]
    return y.astype(dtype)


def rms_head_norm(x, scale, eps=1e-6):
    """qk-norm: RMS norm over the head dim (per head)."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return (y * scale).astype(dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_table(seq_len: int, head_dim: int, theta: float):
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    ang = jnp.outer(t, freqs)                       # (S, half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (..., S, H, D); cos/sin: (S, D/2) or (..., S, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:   # (S, half) -> broadcast over batch and heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    else:               # (B, S, half) e.g. decode positions
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    cos = cos.astype(x.dtype)
    sin = sin.astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(cfg: ArchConfig, key, d_src: Optional[int] = None):
    """d_src: K/V source dim (cross-attention reads from vision states)."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    d_src = d_src or d
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(k1, (d, cfg.n_heads * hd)),
        "wk": _dense_init(k2, (d_src, cfg.n_kv_heads * hd)),
        "wv": _dense_init(k3, (d_src, cfg.n_kv_heads * hd)),
        "wo": _dense_init(k4, (cfg.n_heads * hd, d), fan_in=cfg.n_heads * hd),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _gqa_expand(k, n_heads):
    """(B, S, KV, D) -> (B, S, H, D) by repeating groups."""
    b, s, kv, d = k.shape
    if kv == n_heads:
        return k
    reps = n_heads // kv
    return jnp.repeat(k, reps, axis=2)


def chunked_causal_attention(q, k, v, *, chunk: int = 512):
    """Causal attention in query chunks, O(S*chunk) live memory: chunk i
    takes an exact fp32 softmax over the keys up to its own end, so the
    scores above the diagonal's chunks are never computed (half of S x S
    in all). Differentiated, each chunk's scores are recomputed rather
    than kept. q, k: (B, S, H, D), v: (B, S, H, Dv) (kv already
    GQA-expanded); the scores are scaled by D^-1/2 and materialize in
    fp32."""
    b, s, h, d = q.shape
    nc = max(s // chunk, 1)
    chunk = s // nc
    scale = d ** -0.5

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def block(qb, kb, vb, i):
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                        preferred_element_type=jnp.float32)
        q_pos = i * chunk + jnp.arange(qb.shape[1])
        mask = q_pos[:, None] >= jnp.arange(kb.shape[1])[None, :]
        sc = jnp.where(mask[None, None], sc * scale, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(vb.dtype), vb,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    # the last chunk also takes the rows past nc * chunk
    ends = [(i + 1) * chunk for i in range(nc - 1)] + [s]
    return jnp.concatenate(
        [block(q[:, i * chunk:e], k[:, :e], v[:, :e], i)
         for i, e in enumerate(ends)], axis=1)


def full_causal_attention(q, k, v):
    """Reference O(S^2)-memory attention (tests / tiny shapes); v may have
    a head size of its own."""
    b, s, h, d = q.shape
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    preferred_element_type=jnp.float32) * d ** -0.5
    mask = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def decode_attention(q, k_new, v_new, k_cache, v_cache, cache_len):
    """One new token attending to its slot's cache and to itself.

    q (B, 1, H, D); the token's own k_new, v_new (B, 1, KV, D); the cache
    (B, KV, S, D), read in place, its first ``cache_len`` positions valid.
    One fp32 softmax over the cache and the new token: the same as writing
    the row at ``cache_len`` and attending over ``cache_len + 1``
    positions, without the write.

    Grouped einsums instead of jnp.repeat head expansion: the repeat op
    breaks GSPMD partitioning of a sequence-sharded cache.
    """
    b, _, h, d = q.shape
    kv, skv = k_cache.shape[1], k_cache.shape[2]
    scale = d ** -0.5
    qg = q.reshape(b, kv, h // kv, d)
    sc = jnp.einsum("bkgd,bksd->bkgs", qg, k_cache,
                    preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(skv)[None, :] < cache_len[:, None]    # (B, S)
    sc = jnp.where(valid[:, None, None, :], sc, -jnp.inf)
    sn = jnp.einsum("bkgd,bkd->bkg", qg, k_new[:, 0],
                    preferred_element_type=jnp.float32) * scale
    m = jnp.maximum(sc.max(-1), sn)
    pc = jnp.exp(sc - m[..., None])
    pn = jnp.exp(sn - m)
    l = pc.sum(-1) + pn
    o = jnp.einsum("bkgs,bksd->bkgd", (pc / l[..., None]).astype(v_cache.dtype),
                   v_cache, preferred_element_type=jnp.float32)
    o = o + (pn / l)[..., None] * v_new[:, 0, :, None, :].astype(jnp.float32)
    return o.reshape(b, 1, h, d).astype(q.dtype)


def attention_block(p, x, cfg: ArchConfig, *, rope=None, positions=None,
                    kv_cache=None, cache_len=None, kv_src=None,
                    causal=True, seq_axis=None):
    """Full attention sub-block: proj -> rope -> (qk-norm) -> attn -> out proj.

    kv_cache: None for train/prefill; for decode this layer's (k, v) cache
    of shape (B, KV, S, D), read only: the block returns the token's new
    (B, 1, KV, D) K and V rows for the caller to write at ``cache_len``.
    kv_src: cross-attention source states.
    """
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    cd = x.dtype
    src = kv_src if kv_src is not None else x
    q = (x @ p["wq"].astype(cd)).reshape(b, s, cfg.n_heads, hd)
    k = (src @ p["wk"].astype(cd)).reshape(b, src.shape[1], cfg.n_kv_heads, hd)
    v = (src @ p["wv"].astype(cd)).reshape(b, src.shape[1], cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_head_norm(q, p["q_norm"].astype(cd))
        k = rms_head_norm(k, p["k_norm"].astype(cd))
    if rope is not None and kv_src is None:
        cos, sin = rope
        if positions is not None:        # decode: per-token positions
            cos = jnp.take(cos, positions, axis=0)   # (B, 1, half)
            sin = jnp.take(sin, positions, axis=0)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    new_rows = None
    if kv_cache is not None:             # decode step
        kc, vc = kv_cache
        # the token attends to its rows as the cache will hold them
        k_row, v_row = k.astype(kc.dtype), v.astype(vc.dtype)
        new_rows = (k_row, v_row)
        o = decode_attention(q, k_row.astype(cd), v_row.astype(cd),
                             kc.astype(cd), vc.astype(cd), cache_len)
    elif kv_src is not None:             # cross attention (not causal)
        kq = _gqa_expand(k, cfg.n_heads)
        vq = _gqa_expand(v, cfg.n_heads)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, kq,
                        preferred_element_type=jnp.float32) * hd ** -0.5
        pr = jax.nn.softmax(sc, axis=-1).astype(cd)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, vq)
    else:                                 # train / prefill, causal
        o = causal_attention(q, _gqa_expand(k, cfg.n_heads),
                             _gqa_expand(v, cfg.n_heads))
    out = o.reshape(b, s, cfg.n_heads * hd) @ p["wo"].astype(cd)
    return out, new_rows


def causal_attention(q, k, v):
    """Causal self-attention over a whole sequence; q, k, v (B, S, H, D),
    v's head size may differ from q's and k's. Full attention up to 1024
    positions, query chunks above."""
    if q.shape[1] <= 1024:
        return full_causal_attention(q, k, v)
    return chunked_causal_attention(q, k, v)


# ---------------------------------------------------------------------------
# latent attention (MLA, DeepSeek-V2 arXiv:2405.04434 §2.1; no q latent)
# ---------------------------------------------------------------------------

def init_mla(cfg: ArchConfig, key):
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": _dense_init(k1, (d, h * qk)),
        # the latent c (r) and the one rope key head shared by all heads
        "wkv_a": _dense_init(k2, (d, r + cfg.qk_rope_head_dim)),
        "kv_norm": jnp.ones((r,), jnp.float32),
        "wkv_b": _dense_init(k3, (r, h * (cfg.qk_nope_head_dim
                                          + cfg.v_head_dim))),
        "wo": _dense_init(k4, (h * cfg.v_head_dim, d),
                          fan_in=h * cfg.v_head_dim),
    }


def mla_block(p, x, cfg: ArchConfig, *, rope):
    """q = x W_q, split per head into q_nope and q_pe; [c, k_pe] = x W_kva;
    [k_nope, v] = RMSNorm(c) W_kvb per head (eps 1e-6); RoPE on q_pe and
    on k_pe (one head, shared by all); causal softmax over (q_nope.k_nope
    + q_pe.k_pe) / sqrt(q/k head size); (sum p v) W_o. RoPE rotates
    halves of the rope part (the published code rotates interleaved
    pairs: a fixed permutation of W_q's and W_kva's rope columns)."""
    b, s, _ = x.shape
    h, r = cfg.n_heads, cfg.kv_lora_rank
    nope, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    cd = x.dtype
    cos, sin = rope
    with jax.named_scope("attention"):
        q = (x @ p["wq"].astype(cd)).reshape(b, s, h, -1)
    with jax.named_scope("mla_latent"):
        kva = x @ p["wkv_a"].astype(cd)
        c = rms_head_norm(kva[..., :r], p["kv_norm"])
        kv = (c @ p["wkv_b"].astype(cd)).reshape(b, s, h, nope + dv)
    with jax.named_scope("attention"):
        q = jnp.concatenate([q[..., :nope],
                             apply_rope(q[..., nope:], cos, sin)], -1)
        k_pe = apply_rope(kva[..., None, r:], cos, sin)        # (B,S,1,R)
        k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
            k_pe, (b, s, h, k_pe.shape[-1]))], -1)
        o = causal_attention(q, k, kv[..., nope:])
        return o.reshape(b, s, h * dv) @ p["wo"].astype(cd)


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------

def init_mlp(cfg: ArchConfig, key, d_ff=None):
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": _dense_init(k1, (cfg.d_model, d_ff)),
            "w_up": _dense_init(k2, (cfg.d_model, d_ff)),
            "w_down": _dense_init(k3, (d_ff, cfg.d_model), fan_in=d_ff)}


def mlp_block(p, x, scope="mlp"):
    with jax.named_scope(scope):
        cd = x.dtype
        g = jax.nn.silu(x @ p["w_gate"].astype(cd))
        u = x @ p["w_up"].astype(cd)
        return (g * u) @ p["w_down"].astype(cd)


# ---------------------------------------------------------------------------
# MoE: dropless routing, the experts held here as one grouped product
# ---------------------------------------------------------------------------

# Rows per tile of the grouped product; each expert held adds at most one
# tile of padding to the rows routed to it.
GMM_TILE_ROWS = 128


def init_moe(cfg: ArchConfig, key):
    """The router over all experts and the weights of the experts held
    here; expert e is drawn from ``fold_in(key, e)``, so a share holds the
    uncut layer's experts."""
    m = cfg.moe
    d = cfg.d_model
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    ids = m.first_held + jnp.arange(m.held)

    def experts(k, shape, fan_in=None):
        return jax.vmap(lambda e: _dense_init(jax.random.fold_in(k, e),
                                              shape, fan_in))(ids)

    p = {
        "router": _dense_init(k1, (d, m.n_experts)),
        "w_gate": experts(k2, (d, m.d_ff_expert)),
        "w_up": experts(k3, (d, m.d_ff_expert)),
        "w_down": experts(k4, (m.d_ff_expert, d), fan_in=m.d_ff_expert),
    }
    if m.n_shared_experts:
        p["shared"] = init_mlp(cfg, k5, d_ff=m.n_shared_experts * m.d_ff_shared)
    if m.bias_std is not None:
        p["router_bias"] = m.bias_std * jax.random.normal(
            jax.random.fold_in(k1, 1), (m.n_experts,), jnp.float32)
    return p


def route(xt, p, m):
    """Scores (T, E) in float32, the experts picked (T, k) and their
    weights (T, k): the picked scores over their sum, scaled. The
    correction bias, where there is one, moves the pick and not the
    weights."""
    logits = jnp.dot(xt.astype(jnp.float32), p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if m.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    pick = scores + p["router_bias"] if "router_bias" in p else scores
    _, idx = jax.lax.top_k(pick, m.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return scores, idx, w * m.routed_scaling


def balance_loss(scores, idx, m, n_seq: int):
    """aux_coef * sum_i f_i P_i, f_i = E / (k T) * #{t: i picked}, P_i the
    mean over t of i's score over the token's score sum (DeepSeek-V3 eqs.
    17-20); per sequence and averaged when ``seq_aux``, else over all T."""
    t, e = scores.shape
    g = n_seq if m.seq_aux else 1
    picked = jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(1)
    f = picked.reshape(g, t // g, e).mean(1) * (e / m.top_k)
    share = scores / scores.sum(-1, keepdims=True)
    prob = share.reshape(g, t // g, e).mean(1)
    return m.aux_coef * jnp.mean(jnp.sum(f * prob, -1))


def _gmm_tiling(_rows: int, k: int, n: int):
    """(rows, contraction, columns) tile of the grouped products, forward
    and backward; the columns and contraction stay whole up to 1,536."""
    def tile(x):
        return 512 if x % 512 == 0 else x if x <= 1536 else 128
    return GMM_TILE_ROWS, tile(k), tile(n)


def grouped_matmul(lhs, rhs, sizes):
    """lhs (M, K) rows sorted by group; rhs (G, K, N); sizes (G + 1,), the
    last group's rows not computed. Row r of group g is lhs[r] @ rhs[g];
    rows past the G groups are undefined. The Pallas grouped product
    (megablox ``gmm``) visits only the tiles that hold the groups' rows;
    off the TPU it runs in the Pallas interpreter."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as mb

    rhs = rhs.astype(lhs.dtype)

    def call(interpret):
        return lambda a, b, s: mb.gmm(a, b, s, a.dtype, _gmm_tiling, None,
                                      None, False, interpret)
    return jax.lax.platform_dependent(lhs, rhs, sizes, tpu=call(False),
                                      default=call(True))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _dispatch(xt, order, pos, held, k: int, t: int):
    """Row r: token ``order[r] // k``'s row of xt (rows past the T*k
    pairs are zero). Its transpose takes the first ``held`` rows' parts
    back by ``pos`` and sums each token's k of them: no scatter."""
    return jnp.take(xt, order // k, axis=0, mode="fill", fill_value=0)


def _dispatch_fwd(xt, order, pos, held, k, t):
    return _dispatch(xt, order, pos, held, k, t), (pos, held)


def _dispatch_bwd(k, t, res, g):
    pos, held = res
    pos = pos[:t * k]
    pairs = jnp.where((pos < held)[:, None], jnp.take(g, pos, axis=0), 0)
    return (pairs.reshape(t, k, -1).sum(1, dtype=jnp.float32).astype(g.dtype),
            None, None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(ys, order, pos, held):
    """Pair p (token p // k, choice p % k): sorted row ``pos[p]`` of ys
    where it is one of the first ``held``, else zero. Its transpose
    gathers by ``order``: no scatter."""
    return jnp.where((pos < held)[:, None], jnp.take(ys, pos, axis=0), 0)


def _combine_fwd(ys, order, pos, held):
    return _combine(ys, order, pos, held), (order, held)


def _combine_bwd(res, g):
    order, held = res
    rows = jnp.take(g, order, axis=0, mode="fill", fill_value=0)
    return (jnp.where((jnp.arange(order.shape[0]) < held)[:, None], rows, 0),
            None, None, None)


_combine.defvjp(_combine_fwd, _combine_bwd)


def _tile_rows(sizes, tm: int):
    """Rows the grouped product computes: each group's rows, out to the
    tiles they start and end in."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    rows = (-(-ends // tm) - starts // tm) * tm
    return jnp.sum(jnp.where(sizes > 0, rows, 0))


def _moe_local(x, p, cfg: ArchConfig, e0, n: int):
    """The routed experts ``e0 .. e0 + n - 1`` of one device, dropless.

    Routes every token over all experts, sorts the (token, choice) pairs
    routed to the experts held here by expert, computes them with one
    grouped product per weight and adds each back with its weight. The
    pairs routed elsewhere are left out: on a mesh the caller sums the
    devices' parts. Returns (y, balance loss, rows per expert held, rows
    the grouped product computes)."""
    m = cfg.moe
    b, s, d = x.shape
    t, k = b * s, m.top_k
    rows = -(-t * k // GMM_TILE_ROWS) * GMM_TILE_ROWS
    xt = x.reshape(t, d)
    with jax.named_scope("moe_route"):
        scores, idx, w = route(xt, p, m)
        aux = balance_loss(scores, idx, m, b)
        gid = idx.reshape(t * k)
        here = (gid >= e0) & (gid < e0 + n)
        lid = jnp.pad(jnp.where(here, gid - e0, n), (0, rows - t * k),
                      constant_values=n)
        sizes = jnp.bincount(lid, length=n + 1).astype(jnp.int32)
        held = jnp.sum(sizes[:n])
    with jax.named_scope("moe_dispatch"):
        # the pairs held here first, by expert; rows past ``held`` are
        # never read back, and take no gradient
        order = jnp.argsort(lid, stable=True).astype(jnp.int32)
        pos = jnp.zeros_like(order).at[order].set(
            jnp.arange(rows, dtype=jnp.int32))
        xs = _dispatch(xt, order, pos, held, k, t)
    with jax.named_scope("moe_experts"):
        h = jax.nn.silu(grouped_matmul(xs, p["w_gate"], sizes)) \
            * grouped_matmul(xs, p["w_up"], sizes)
        ys = grouped_matmul(h, p["w_down"], sizes)
    with jax.named_scope("moe_combine"):
        yp = _combine(ys, order, pos[:t * k], held).reshape(t, k, d)
        y = jnp.einsum("tkd,tk->td", yp, w.astype(x.dtype),
                       preferred_element_type=jnp.float32).astype(x.dtype)
    return (y.reshape(b, s, d), aux, sizes[:n],
            _tile_rows(sizes[:n], GMM_TILE_ROWS))


def moe_block(p, x, cfg: ArchConfig):
    """Dropless MoE over the experts held here, plus the shared experts.
    Returns (y, balance loss, counters).

    On a mesh the layer runs per device in a shard_map (its grouped
    products are Pallas kernels, which the partitioner cannot split):
    tokens stay on their data shard where the batch divides the batch
    axes, and the held experts shard over "model" where they divide it
    (expert parallelism). Every device routes its tokens over all experts
    and computes its own experts' pairs (``_moe_local``); the only
    collective is the activation-sized psum of the partial outputs."""
    from repro.sharding.rules import current_rules

    m = cfg.moe
    rules = current_rules()
    mesh = rules.mesh if rules else None
    router = {k: p[k] for k in ("router", "router_bias") if k in p}
    experts = (p["w_gate"], p["w_up"], p["w_down"])
    if mesh is None or mesh.size == 1:
        y, aux, sizes, gemm = _moe_local(
            x, dict(router, w_gate=experts[0], w_up=experts[1],
                    w_down=experts[2]), cfg, m.first_held, m.held)
        held, most = jnp.sum(sizes), jnp.max(sizes)
    else:
        y, aux, held, gemm, most = _moe_sharded(
            x, router, experts, cfg, mesh, rules.table.get("batch", ()))
    stats = {"moe/held_rows": held, "moe/gemm_rows": gemm,
             "moe/max_expert_rows": most}
    if m.n_shared_experts:
        y = y + mlp_block(p["shared"], x, scope="shared_expert")
    return y, aux, stats


def _moe_sharded(x, router, experts, cfg: ArchConfig, mesh, batch_axes):
    m = cfg.moe
    bsz = 1
    for a in batch_axes:
        bsz *= int(mesh.shape[a])
    data = tuple(batch_axes) if batch_axes and x.shape[0] % bsz == 0 else ()
    n_model = int(mesh.shape["model"]) if "model" in mesh.axis_names else 1
    ep = "model" if n_model > 1 and m.held % n_model == 0 else None
    n_local = m.held // n_model if ep else m.held
    split = data + ((ep,) if ep else ())     # the axes that split the work

    def body(xl, router, wg, wu, wd):
        e0 = m.first_held
        if ep:
            e0 = e0 + jax.lax.axis_index(ep) * n_local
        y, aux, sizes, gemm = _moe_local(
            xl, dict(router, w_gate=wg, w_up=wu, w_down=wd), cfg, e0,
            n_local)
        held = jnp.sum(sizes)
        if data:                # an expert's rows come from every data shard
            sizes = jax.lax.psum(sizes, data)
        most = jnp.max(sizes)
        if ep:
            y = jax.lax.psum(y, ep)
            most = jax.lax.pmax(most, ep)
        if split:
            held, gemm = jax.lax.psum(held, split), jax.lax.psum(gemm, split)
        return y, jax.lax.pmean(aux, mesh.axis_names), held, gemm, most

    tokens = P(data if len(data) > 1 else (data[0] if data else None),
               None, None)
    held_w = P(ep, None, None)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(tokens, P(), held_w, held_w, held_w),
        out_specs=(tokens, P(), P(), P(), P()),
        check_vma=False,
    )(x, router, *experts)
