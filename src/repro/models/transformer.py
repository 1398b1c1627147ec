"""Unified stacked model over heterogeneous block types.

Layouts (keeps HLO size ~one layer body regardless of depth):
  uniform : one ``lax.scan`` over all (stacked-param) layers
            -> dense, moe, rwkv archs; an MoE model's leading dense
            layers (``first_k_dense``) are a scanned stack of their own
            that runs first
  periodic: outer scan over periods of [inner scan of k homogeneous layers +
            one special layer], + trailing inner layers
            -> vlm   (4 dense + 1 cross-attn) x 8
            -> hybrid(5 mamba + 1 *shared* attn block) x 13 + 3 mamba

Decode state is a pytree with the same stacking as the params. Attention
caches, (B, KV, S, D) per layer, go into the scans as xs and are only read
there: each layer emits its new K/V rows as ys, and one scatter after the
scans writes them in place (``_write_rows``). Recurrent states (rwkv,
mamba) are rewritten whole each step and go through the scans as xs/ys.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import blocks as B
from repro.models import mamba as M
from repro.models import rwkv as R
from repro.sharding import constrain

# ---------------------------------------------------------------------------
# layout
# ---------------------------------------------------------------------------

def build_layout(cfg: ArchConfig) -> dict:
    if cfg.family == "vlm":
        k = cfg.cross_attn_every
        periods = cfg.n_layers // k
        trailing = cfg.n_layers - periods * k
        return {"kind": "periodic", "periods": periods, "inner_n": k - 1,
                "inner_block": "dense", "single_block": "cross_attn",
                "trailing": trailing}
    if cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        periods = cfg.n_layers // k
        trailing = cfg.n_layers - periods * k
        return {"kind": "periodic", "periods": periods, "inner_n": k - 1,
                "inner_block": "mamba", "single_block": "shared_attn",
                "trailing": trailing}
    block = {"ssm": "rwkv"}.get(cfg.family)
    if block is None:
        block = "moe" if cfg.moe is not None else "dense"
    # leading dense layers of an MoE model: a stack of their own, first
    lead = cfg.first_k_dense if block == "moe" else 0
    return {"kind": "uniform", "block": block, "n": cfg.n_layers - lead,
            "lead": lead}


# ---------------------------------------------------------------------------
# single-layer init / forward
# ---------------------------------------------------------------------------

def init_layer(block: str, cfg: ArchConfig, key):
    k1, k2, k3 = jax.random.split(key, 3)
    init_attn = B.init_mla if cfg.mla else B.init_attention
    if block == "dense" or block == "shared_attn":
        return {"attn": init_attn(cfg, k1),
                "mlp": B.init_mlp(cfg, k2),
                "ln1": B.init_norm(cfg), "ln2": B.init_norm(cfg)}
    if block == "moe":
        return {"attn": init_attn(cfg, k1),
                "moe": B.init_moe(cfg, k2),
                "ln1": B.init_norm(cfg), "ln2": B.init_norm(cfg)}
    if block == "cross_attn":
        return {"attn": B.init_attention(cfg, k1, d_src=cfg.vision_dim),
                "mlp": B.init_mlp(cfg, k2),
                "ln1": B.init_norm(cfg), "ln2": B.init_norm(cfg),
                "gate_attn": jnp.zeros((), jnp.float32),
                "gate_mlp": jnp.zeros((), jnp.float32)}
    if block == "rwkv":
        return {"tm": R.init_rwkv_layer(cfg, k1),
                "ln1": B.init_norm(cfg), "ln2": B.init_norm(cfg)}
    if block == "mamba":
        return {"m": M.init_mamba_layer(cfg, k1),
                "ln1": B.init_norm(cfg)}
    raise ValueError(block)


ATTN_BLOCKS = ("dense", "moe", "shared_attn")


def zero_aux(cfg: ArchConfig) -> dict:
    """What a layer adds to the step's metrics: the balance loss and, in
    an MoE model, the routing counters (``moe/...``)."""
    aux = {"aux_loss": jnp.zeros((), jnp.float32)}
    if cfg.moe is not None:
        aux.update({k: jnp.zeros((), jnp.int32) for k in (
            "moe/held_rows", "moe/gemm_rows", "moe/max_expert_rows")})
    return aux


def add_aux(a: dict, b: dict) -> dict:
    """Sums over layers; the largest expert load is the most of any."""
    return {k: jnp.maximum(a[k], b[k]) if k == "moe/max_expert_rows"
            else a[k] + b[k] for k in a}


def layer_fwd(block: str, p, x, cfg: ArchConfig, ctx: dict,
              state=None, collect_kv: bool = False):
    """Returns (x, new_state, aux, kv_out), aux as ``zero_aux``. In
    decode, an attention block's new_state is its new (k, v) rows, a
    cross-attention block's None."""
    aux = zero_aux(cfg)
    kv_out = None
    decode = ctx["mode"] == "decode"
    if block in ATTN_BLOCKS:
        h = B.apply_norm(p["ln1"], x, cfg)
        kv_cache = state if decode else None
        new_rows = None
        if cfg.mla:
            o = B.mla_block(p["attn"], h, cfg, rope=ctx["rope"],
                            attn_impl=ctx.get("attn_impl", "xla"))
        else:
            with jax.named_scope("attention"):
                o, new_rows = B.attention_block(
                    p["attn"], h, cfg, rope=ctx.get("rope"),
                    positions=ctx.get("positions"),
                    kv_cache=kv_cache, cache_len=ctx.get("cache_len"),
                    attn_impl=ctx.get("attn_impl", "xla"))
        x = x + o
        h = B.apply_norm(p["ln2"], x, cfg)
        if block == "moe":
            y, loss, stats = B.moe_block(p["moe"], h, cfg)
            aux = dict(stats, aux_loss=loss)
        else:
            y = B.mlp_block(p["mlp"], h)
        x = x + y
        x = constrain(x, ("batch", None, None))
        return x, new_rows, aux, kv_out
    if block == "cross_attn":
        h = B.apply_norm(p["ln1"], x, cfg)
        if decode:
            kv, vv = state          # precomputed vision K/V (B, KV, Nv, D)
            hd = cfg.resolved_head_dim
            b_, s_, _ = h.shape     # s_ == 1
            q = (h @ p["attn"]["wq"].astype(h.dtype)).reshape(
                b_, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, hd)
            if cfg.qk_norm:
                q = B.rms_head_norm(q, p["attn"]["q_norm"].astype(h.dtype))
            sc = jnp.einsum("bkgd,bksd->bkgs", q, kv.astype(h.dtype),
                            preferred_element_type=jnp.float32) * hd ** -0.5
            pr = jax.nn.softmax(sc, -1).astype(h.dtype)
            o = jnp.einsum("bkgs,bksd->bkgd", pr, vv.astype(h.dtype))
            o = o.reshape(b_, s_, cfg.n_heads * hd) @ \
                p["attn"]["wo"].astype(h.dtype)
        else:
            o, _ = B.attention_block(p["attn"], h, cfg,
                                     kv_src=ctx["vision"].astype(h.dtype))
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * o
        h = B.apply_norm(p["ln2"], x, cfg)
        x = x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * B.mlp_block(p["mlp"], h)
        x = constrain(x, ("batch", None, None))
        return x, None, aux, None
    if block == "rwkv":
        h = B.apply_norm(p["ln1"], x, cfg)
        if decode:
            wkv, tm_last, cm_last = state
            o, new_wkv = R.rwkv_time_mix(p["tm"], h, cfg, state=wkv,
                                         last_x=tm_last)
            new_tm_last = h[:, -1:]
            x = x + o
            h2 = B.apply_norm(p["ln2"], x, cfg)
            x = x + R.rwkv_channel_mix(p["tm"], h2, last_x=cm_last)
            new_state = (new_wkv, new_tm_last, h2[:, -1:])
        else:
            o, _ = R.rwkv_time_mix(p["tm"], h, cfg)
            x = x + o
            h2 = B.apply_norm(p["ln2"], x, cfg)
            x = x + R.rwkv_channel_mix(p["tm"], h2)
            new_state = None
        x = constrain(x, ("batch", None, None))
        return x, new_state, aux, None
    if block == "mamba":
        h = B.apply_norm(p["ln1"], x, cfg)
        o, new_state = M.mamba_block(p["m"], h, cfg, state=state)
        x = x + o
        x = constrain(x, ("batch", None, None))
        return x, new_state, aux, None
    raise ValueError(block)


# ---------------------------------------------------------------------------
# stacked init
# ---------------------------------------------------------------------------

def _stack_init(block: str, cfg: ArchConfig, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_layer(block, cfg, k))(keys)


def init_stack(cfg: ArchConfig, key):
    layout = build_layout(cfg)
    if layout["kind"] == "uniform":
        out = {"layers": _stack_init(layout["block"], cfg, key, layout["n"])}
        if layout["lead"]:
            out["lead_layers"] = _stack_init(
                "dense", cfg, jax.random.fold_in(key, 1), layout["lead"])
        return out
    k1, k2, k3, k4 = jax.random.split(key, 4)
    periods, inner_n = layout["periods"], layout["inner_n"]
    inner = jax.vmap(lambda k: _stack_init(layout["inner_block"], cfg, k,
                                           inner_n))(
        jax.random.split(k1, periods))
    out = {"layers": {"inner": inner,
                      "trailing": _stack_init(layout["inner_block"], cfg, k2,
                                              max(layout["trailing"], 1))}}
    if layout["single_block"] == "cross_attn":
        out["layers"]["single"] = _stack_init("cross_attn", cfg, k3, periods)
    else:   # hybrid: ONE shared attn block
        out["shared_block"] = init_layer("shared_attn", cfg, k4)
    return out


# ---------------------------------------------------------------------------
# stacked forward
# ---------------------------------------------------------------------------

def _maybe_remat(fn, ctx):
    pol = ctx.get("remat")
    if ctx["mode"] != "train" or pol in (None, "none"):
        return fn
    if pol == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def _scan_layers(block: str, stacked, x, cfg, ctx, states=None,
                 collect_kv=False):
    """Scan homogeneous stacked layers. Returns (x, aux, outs, kvs); in
    decode ``outs`` stacks each layer's ``layer_fwd`` new_state, for
    ``_commit``."""
    decode = ctx["mode"] == "decode"

    if decode:
        def body(carry, xs):
            x, aux = carry
            p, st = xs
            x, out, a, _ = layer_fwd(block, p, x, cfg, ctx, st)
            return (x, add_aux(aux, a)), out
        (x, aux), outs = jax.lax.scan(
            body, (x, zero_aux(cfg)), (stacked, states))
        return x, aux, outs, None

    def body(carry, p):
        x, aux = carry
        x, _, a, kv = layer_fwd(block, p, x, cfg, ctx, None, collect_kv)
        return (x, add_aux(aux, a)), kv
    body = _maybe_remat(body, ctx)
    (x, aux), kvs = jax.lax.scan(body, (x, zero_aux(cfg)), stacked)
    return x, aux, None, kvs


def _write_rows(cache, rows, cache_len):
    """Write rows (*stack, B, 1, KV, D) into the stacked cache
    (*stack, B, KV, S, D) at position ``cache_len[b]``.

    The scatter is indexed on every axis but D, so its update window is one
    row: with the cache donated XLA writes it in place and copies nothing
    cache-sized. A position past the buffer is dropped."""
    rows = rows[..., 0, :, :]
    shape = rows.shape[:-1]                      # (*stack, B, KV)
    idx = [jax.lax.broadcasted_iota(jnp.int32, shape, i)
           for i in range(len(shape))]
    pos = jnp.broadcast_to(cache_len[:, None], shape)
    return cache.at[(*idx, pos)].set(rows.astype(cache.dtype), mode="drop",
                                     unique_indices=True)


def _commit(block: str, states, outs, cache_len):
    """A stack's decode state after the step, from the scans' ``outs``."""
    if block in ATTN_BLOCKS:
        with jax.named_scope("cache_insert"):
            return tuple(_write_rows(c, r, cache_len)
                         for c, r in zip(states, outs))
    if block == "cross_attn":
        return states           # vision K/V, fixed for the request
    return outs                 # recurrent states, rewritten whole


def apply_stack(params, x, cfg: ArchConfig, ctx: dict, states=None):
    """Run all layers. states: decode-state pytree or None.

    Returns (x, aux, new_states)."""
    layout = build_layout(cfg)
    cache_len = ctx.get("cache_len")
    if layout["kind"] == "uniform":
        lead = zero_aux(cfg)
        if layout["lead"]:
            x, lead, _, _ = _scan_layers("dense", params["lead_layers"], x,
                                         cfg, ctx)
        x, aux, outs, _ = _scan_layers(
            layout["block"], params["layers"], x, cfg, ctx,
            None if states is None else states["layers"])
        aux = add_aux(lead, aux)
        if states is None:
            return x, aux, None
        return x, aux, {"layers": _commit(layout["block"], states["layers"],
                                          outs, cache_len)}

    periods = layout["periods"]
    inner_block = layout["inner_block"]
    single_block = layout["single_block"]
    decode = ctx["mode"] == "decode"
    shared_p = params.get("shared_block")
    aux0 = zero_aux(cfg)

    if decode:
        def outer(carry, xs):
            x, aux = carry
            if single_block == "cross_attn":
                (inner_p, single_p), (inner_st, single_st) = xs
            else:
                inner_p, (inner_st, single_st) = xs
                single_p = shared_p
            x, a1, inner_out, _ = _scan_layers(
                inner_block, inner_p, x, cfg, ctx, inner_st)
            x, single_out, a2, _ = layer_fwd(
                single_block, single_p, x, cfg, ctx, single_st)
            return (x, add_aux(add_aux(aux, a1), a2)), (inner_out,
                                                        single_out)

        if single_block == "cross_attn":
            xs = ((params["layers"]["inner"], params["layers"]["single"]),
                  (states["inner"], states["single"]))
        else:
            xs = (params["layers"]["inner"],
                  (states["inner"], states["single"]))
        (x, aux), (inner_out, single_out) = jax.lax.scan(outer, (x, aux0), xs)
        new_states = {
            "inner": _commit(inner_block, states["inner"], inner_out,
                             cache_len),
            "single": _commit(single_block, states["single"], single_out,
                              cache_len),
            "trailing": states["trailing"]}
        if layout["trailing"]:
            x, a3, tr_out, _ = _scan_layers(
                inner_block, params["layers"]["trailing"], x, cfg, ctx,
                states["trailing"])
            aux = add_aux(aux, a3)
            new_states["trailing"] = _commit(inner_block, states["trailing"],
                                             tr_out, cache_len)
        return x, aux, new_states

    def outer(carry, xs):
        x, aux = carry
        if single_block == "cross_attn":
            inner_p, single_p = xs
        else:
            inner_p, single_p = xs, shared_p
        x, a1, _, _ = _scan_layers(inner_block, inner_p, x, cfg, ctx)
        x, _, a2, _ = layer_fwd(single_block, single_p, x, cfg, ctx)
        return (x, add_aux(add_aux(aux, a1), a2)), None

    if single_block == "cross_attn":
        xs = (params["layers"]["inner"], params["layers"]["single"])
    else:
        xs = params["layers"]["inner"]
    (x, aux), _ = jax.lax.scan(outer, (x, aux0), xs)
    if layout["trailing"]:
        x, a3, _, _ = _scan_layers(inner_block,
                                   params["layers"]["trailing"], x, cfg, ctx)
        aux = add_aux(aux, a3)
    return x, aux, None


# ---------------------------------------------------------------------------
# decode-state init
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ArchConfig, batch: int, buffer_len: int,
                      dtype=jnp.bfloat16, vision=None, params=None):
    """Zeroed decode state (cache buffers) for the whole stack."""
    if cfg.mla:
        raise ValueError(
            f"{cfg.name}: decoding with latent attention (MLA) needs a "
            f"latent K/V cache, which the decode path does not have yet")
    hd = cfg.resolved_head_dim
    layout = build_layout(cfg)

    def attn_state():
        shape = (batch, cfg.n_kv_heads, buffer_len, hd)
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

    def rwkv_state():
        h = cfg.d_model // cfg.rwkv.head_dim
        return (jnp.zeros((batch, h, cfg.rwkv.head_dim, cfg.rwkv.head_dim),
                          jnp.float32),
                jnp.zeros((batch, 1, cfg.d_model), dtype),
                jnp.zeros((batch, 1, cfg.d_model), dtype))

    def mamba_state():
        mc = cfg.mamba
        nh = mc.n_heads(cfg.d_model)
        conv_ch = mc.d_inner(cfg.d_model) + 2 * mc.n_groups * mc.d_state
        return (jnp.zeros((batch, nh, mc.d_state, mc.head_dim), jnp.float32),
                jnp.zeros((batch, mc.d_conv - 1, conv_ch), dtype))

    def cross_state(single_p):
        # precompute vision K/V from params (requires params + vision)
        b_, nv, _ = vision.shape
        k = (vision @ single_p["attn"]["wk"].astype(vision.dtype)).reshape(
            b_, nv, cfg.n_kv_heads, hd)
        v = (vision @ single_p["attn"]["wv"].astype(vision.dtype)).reshape(
            b_, nv, cfg.n_kv_heads, hd)
        return (jnp.swapaxes(k, 1, 2).astype(dtype),
                jnp.swapaxes(v, 1, 2).astype(dtype))

    def stack_states(maker, n):
        one = maker()
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), one)

    if layout["kind"] == "uniform":
        maker = {"dense": attn_state, "moe": attn_state,
                 "rwkv": rwkv_state}.get(layout["block"], attn_state)
        return {"layers": stack_states(maker, layout["n"])}

    periods, inner_n = layout["periods"], layout["inner_n"]
    inner_maker = mamba_state if layout["inner_block"] == "mamba" \
        else attn_state
    inner = stack_states(lambda: jax.tree.map(
        lambda a: jnp.broadcast_to(a, (inner_n,) + a.shape), inner_maker()),
        periods)
    if layout["single_block"] == "cross_attn":
        singles = jax.vmap(cross_state)(params["layers"]["single"])
    else:
        singles = stack_states(attn_state, periods)
    trailing = stack_states(inner_maker, max(layout["trailing"], 1))
    return {"inner": inner, "single": singles, "trailing": trailing}
