"""Flash-decode attention (one query token vs a long KV cache) — TPU Pallas.

The GPU flash-decode splits KV across SMs and merges per-split LSE; on TPU
the innermost sequential grid dimension IS the split walk, so the running
(m, l, acc) in VMEM scratch performs the LSE merge incrementally. Invalid
cache positions (>= cache_len) are masked inside each block.

Layout: q (B, H, D); k/v cache (B, KV, S, D) blocked (1,1,block_k,D);
cache_len (B,) is scalar-prefetched into SMEM (a per-row scalar has no
legal VMEM block). Grid (B, H, S // block_k).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, block_k: int, sm_scale: float,
                   n_blocks: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    f32 = jnp.float32
    q = q_ref[0, 0].astype(f32) * sm_scale        # (1, D)  — kept 2D
    k = k_ref[0, 0].astype(f32)                   # (bk, D)
    v = v_ref[0, 0].astype(f32)
    clen = len_ref[pl.program_id(0)]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=f32)  # (1, bk)
    pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    s = jnp.where(pos < clen, s, NEG_INF)

    # running stats live in column 0 of (1, 128) scratch, kept as (1,)
    # vectors: Mosaic cannot store a scalar to VMEM
    m_prev = m_scr[:, 0]                           # (1,)
    m_cur = jnp.maximum(m_prev, s.max(axis=1))
    corr = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])                # (1, bk)
    l_scr[:, 0] = l_scr[:, 0] * corr + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=f32)
    m_scr[:, 0] = m_cur

    @pl.when(ki == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, 0], 1e-37)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_bhd(q, k_cache, v_cache, cache_len, *,
                         block_k: int = 512, sm_scale=None,
                         interpret: bool = False):
    """q: (B, H, D); caches (B, KV, S, D); cache_len (B,) -> (B, H, D)."""
    b, h, d = q.shape
    kv, s = k_cache.shape[1], k_cache.shape[2]
    group = h // kv
    block_k = min(block_k, s)
    assert s % block_k == 0
    nk = s // block_k
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    q4 = q.reshape(b, h, 1, d)
    kv_spec = pl.BlockSpec((1, 1, block_k, d),
                           lambda b_, h_, ki, lens: (b_, h_ // group, ki, 0))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=block_k,
                          sm_scale=sm_scale, n_blocks=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, nk),
            in_specs=[
                pl.BlockSpec((1, 1, 1, d),
                             lambda b_, h_, ki, lens: (b_, h_, 0, 0)),
                kv_spec, kv_spec,
            ],
            out_specs=pl.BlockSpec((1, 1, 1, d),
                                   lambda b_, h_, ki, lens: (b_, h_, 0, 0)),
            scratch_shapes=[pltpu.VMEM((1, 128), jnp.float32),
                            pltpu.VMEM((1, 128), jnp.float32),
                            pltpu.VMEM((1, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        interpret=interpret,
    )(cache_len.astype(jnp.int32), q4, k_cache, v_cache)
    return out.reshape(b, h, d)
