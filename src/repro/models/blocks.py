"""Core transformer blocks: norms, RoPE, GQA attention (chunked online-softmax
XLA path + pluggable Pallas path), SwiGLU MLP, GShard-style MoE.

All blocks are pure functions over param pytrees (dicts of jnp arrays).
Params live in fp32; forward casts to ``compute_dtype`` at block entry.
"""
from __future__ import annotations

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


def chunked_causal_attention(q, k, v, *, chunk: int = 512,
                             logit_dtype=jnp.float32):
    """Online-softmax causal attention, scanning KV chunks (flash-style,
    O(S*chunk) live memory). q,k,v: (B, S, H, D) (kv already GQA-expanded).

    Baseline schedule computes every (q, kv-chunk) pair and masks above the
    diagonal (2x score-FLOP waste vs causal optimum; see EXPERIMENTS.md §Perf
    for the tournament schedule that removes it on the hot cells).
    """
    b, s, h, d = q.shape
    scale = d ** -0.5
    nc = max(s // chunk, 1)
    chunk = s // nc
    qf = jnp.swapaxes(q, 1, 2) * scale            # (B, H, S, D)
    kc = jnp.swapaxes(k, 1, 2).reshape(b, h, nc, chunk, d)
    vc = jnp.swapaxes(v, 1, 2).reshape(b, h, nc, chunk, d)
    kc = jnp.moveaxis(kc, 2, 0)                   # (nc, B, H, C, D)
    vc = jnp.moveaxis(vc, 2, 0)
    q_pos = jnp.arange(s)

    def body(carry, xs):
        m, l, o = carry
        kb, vb, idx = xs
        # score blocks materialize at logit_dtype (fp32 default; bf16 under
        # §Perf A8 — running stats below are ALWAYS fp32)
        sc = jnp.einsum("bhqd,bhkd->bhqk", qf, kb,
                        preferred_element_type=logit_dtype)
        k_pos = idx * chunk + jnp.arange(chunk)
        mask = q_pos[:, None] >= k_pos[None, :]
        scf = jnp.where(mask[None, None], sc.astype(jnp.float32), -jnp.inf)
        m_new = jnp.maximum(m, scf.max(-1))
        p = jnp.exp(scf - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l, o), None

    m0 = jnp.full((b, h, s), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    o0 = jnp.zeros((b, h, s, d), jnp.float32)
    (m, l, o), _ = jax.lax.scan(body, (m0, l0, o0),
                                (kc, vc, jnp.arange(nc)))
    o = o / jnp.maximum(l, 1e-37)[..., None]
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)   # (B, S, H, D)


def full_causal_attention(q, k, v):
    """Reference O(S^2)-memory attention (tests / tiny shapes)."""
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
                    causal=True, attn_impl="xla", seq_axis=None):
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
        kq = _gqa_expand(k, cfg.n_heads)
        vq = _gqa_expand(v, cfg.n_heads)
        if attn_impl == "pallas":
            from repro.kernels import ops as kops
            o = kops.flash_attention(q, kq, vq, causal=True)
        elif attn_impl == "pallas-interpret":
            from repro.kernels import ops as kops
            o = kops.flash_attention(q, kq, vq, causal=True, interpret=True)
        elif attn_impl == "xla-bf16-logits" and s > 1024:
            # §Perf A8: materialize per-chunk score blocks in bf16 (the
            # online-softmax running stats stay fp32); on TPU the Pallas
            # kernel keeps scores in VMEM entirely — this is the XLA-path
            # approximation of that traffic saving
            o = chunked_causal_attention(q, kq, vq,
                                         logit_dtype=jnp.bfloat16)
        elif s <= 1024:
            o = full_causal_attention(q, kq, vq)
        else:
            o = chunked_causal_attention(q, kq, vq)
    out = o.reshape(b, s, cfg.n_heads * hd) @ p["wo"].astype(cd)
    return out, new_rows


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------

def init_mlp(cfg: ArchConfig, key, d_ff=None):
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": _dense_init(k1, (cfg.d_model, d_ff)),
            "w_up": _dense_init(k2, (cfg.d_model, d_ff)),
            "w_down": _dense_init(k3, (d_ff, cfg.d_model), fan_in=d_ff)}


def mlp_block(p, x):
    with jax.named_scope("mlp"):
        cd = x.dtype
        g = jax.nn.silu(x @ p["w_gate"].astype(cd))
        u = x @ p["w_up"].astype(cd)
        return (g * u) @ p["w_down"].astype(cd)


# ---------------------------------------------------------------------------
# MoE (GShard-style capacity-based dense dispatch)
# ---------------------------------------------------------------------------

def init_moe(cfg: ArchConfig, key):
    m = cfg.moe
    d = cfg.d_model
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p = {
        "router": _dense_init(k1, (d, m.n_experts)),
        "w_gate": _dense_init(k2, (m.n_experts, d, m.d_ff_expert)),
        "w_up": _dense_init(k3, (m.n_experts, d, m.d_ff_expert)),
        "w_down": _dense_init(k4, (m.n_experts, m.d_ff_expert, d),
                              fan_in=m.d_ff_expert),
    }
    if m.n_shared_experts:
        p["shared"] = init_mlp(cfg, k5, d_ff=m.n_shared_experts * m.d_ff_shared)
    return p


def _moe_local(x, router, wg, wu, wd, cfg: ArchConfig, e0, n_local: int,
               mesh_axes: tuple, shared_w=None):
    """Per-device MoE core: local routing + local scatter into THIS device's
    expert buffer + local expert GEMMs + gather-back; partial outputs are
    psum'd over the model axis (the only EP collective: activation-sized).

    x: (B_loc, S, D) local tokens; wg/wu/wd: (n_local, d, ff) local experts;
    e0: first local expert id (traced); mesh_axes: (model_axis?, all_axes)
    — empty tuples outside shard_map (single-device path, e0=0,
    n_local=E).
    """
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    cd = x.dtype
    xt = x.reshape(t, d)
    logits = (xt @ router.astype(cd)).astype(jnp.float32)     # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, m.top_k)       # (T, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    capacity = max(int(m.capacity_factor * m.top_k * t / m.n_experts), 4)

    # position of each (token, choice) within its GLOBAL expert queue —
    # identical on every model shard (replicated routing compute)
    onehot = (gate_idx.reshape(t * m.top_k)[:, None] ==
              jnp.arange(m.n_experts)[None, :])               # (T*k, E)
    pos = jnp.cumsum(onehot.astype(jnp.int32), axis=0) - 1
    pos_in_expert = jnp.where(onehot, pos, 0).max(-1)         # (T*k,)
    keep = pos_in_expert < capacity
    gid = gate_idx.reshape(t * m.top_k)

    # local scatter: only (token, choice) pairs routed to THIS device's
    # experts land in the buffer; everything else is OOB-dropped
    local_ok = keep & (gid >= e0) & (gid < e0 + n_local)
    dest = jnp.where(local_ok, (gid - e0) * capacity + pos_in_expert,
                     n_local * capacity)
    updates = jnp.broadcast_to(xt[:, None, :], (t, m.top_k, d)) \
        .reshape(t * m.top_k, d)
    buf = jnp.zeros((n_local * capacity, d), cd)
    buf = buf.at[dest].add(updates, mode="drop")
    bufE = buf.reshape(n_local, capacity, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", bufE, wg.astype(cd))) \
        * jnp.einsum("ecd,edf->ecf", bufE, wu.astype(cd))
    ye = jnp.einsum("ecf,efd->ecd", h, wd.astype(cd))         # (E_loc,C,D)

    yflat = ye.reshape(n_local * capacity, d)
    ygath = yflat.at[dest].get(mode="fill", fill_value=0)     # (T*k, D)
    w = (gate_vals.reshape(t * m.top_k)
         * local_ok.astype(jnp.float32)).astype(cd)
    y = (ygath * w[:, None]).reshape(t, m.top_k, d).sum(1)

    model_axis, all_axes = mesh_axes
    if shared_w is not None:
        # fused shared expert: this device's ff slice contributes a partial
        # sum that rides the EP psum below (one collective, not two)
        sg, su, sd_ = shared_w
        hs = jax.nn.silu(xt @ sg.astype(cd)) * (xt @ su.astype(cd))
        y = y + hs @ sd_.astype(cd)
    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)                       # EP combine

    # load-balance aux loss (Switch style), replicated across the mesh
    me = probs.mean(0)
    ce = onehot.reshape(t, m.top_k, m.n_experts).astype(
        jnp.float32).sum(1).mean(0) * m.top_k
    aux = m.router_aux_coef * m.n_experts * jnp.sum(me * ce)
    if all_axes:
        aux = jax.lax.pmean(aux, all_axes)
    return y.reshape(b, s, d), aux


def moe_block(p, x, cfg: ArchConfig, *, capacity: Optional[int] = None):
    """Top-k capacity MoE. Returns (y, aux_loss).

    On a mesh: expert-parallel shard_map — experts shard over the model
    axis, tokens stay on their data shard, dispatch scatter/gather is
    device-local, and the only collective is an activation-sized psum.
    (The GShard dense-dispatch einsum costs O(T*E*C*D) MXU FLOPs —
    measured 200x the expert GEMMs on olmoe — and GSPMD cannot partition a
    scatter indexed on the sharded expert dim without replicating the
    buffers; the explicit shard_map path avoids both. See DESIGN.md §5.)
    """
    from repro.sharding.rules import current_rules

    m = cfg.moe
    rules = current_rules()
    mesh = rules.mesh if rules else None
    use_shard_map = False
    if mesh is not None and "model" in mesh.axis_names:
        model_size = int(mesh.shape["model"])
        batch_axes = rules.table.get("batch", ())
        bsz = 1
        for a in batch_axes:
            bsz *= int(mesh.shape[a])
        use_shard_map = (m.n_experts % model_size == 0
                         and x.shape[0] % bsz == 0 and model_size > 1)

    if not use_shard_map:
        y, aux = _moe_local(x, p["router"], p["w_gate"], p["w_up"],
                            p["w_down"], cfg, 0, m.n_experts, (None, ()))
        if m.n_shared_experts:
            y = y + mlp_block(p["shared"], x)
        return y, aux

    n_local = m.n_experts // model_size
    b_ax = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    fuse = bool(m.n_shared_experts and m.fuse_shared)

    if fuse:
        def body(xl, router, wg, wu, wd, sg, su, sd_):
            e0 = jax.lax.axis_index("model") * n_local
            return _moe_local(xl, router, wg, wu, wd, cfg, e0, n_local,
                              ("model", mesh.axis_names),
                              shared_w=(sg, su, sd_))

        y, aux = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(b_ax, None, None), P(None, None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None),
                      P(None, "model"), P(None, "model"),
                      P("model", None)),
            out_specs=(P(b_ax, None, None), P()),
            check_vma=False,
        )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"],
          p["shared"]["w_gate"], p["shared"]["w_up"],
          p["shared"]["w_down"])
        return y, aux

    def body(xl, router, wg, wu, wd):
        e0 = jax.lax.axis_index("model") * n_local
        return _moe_local(xl, router, wg, wu, wd, cfg, e0, n_local,
                          ("model", mesh.axis_names))

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(b_ax, None, None), P(None, None),
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=(P(b_ax, None, None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if m.n_shared_experts:
        y = y + mlp_block(p["shared"], x)
    return y, aux
