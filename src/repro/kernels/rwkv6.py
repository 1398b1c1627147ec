"""RWKV-6 (WKV6) chunked linear-recurrence — TPU Pallas kernel.

Hardware adaptation (DESIGN.md §3): the reference CUDA kernel walks the
recurrence one token per thread-block with the state in registers; that maps
terribly to TPU. Instead we use the chunk-parallel matrix form: per chunk,
the intra-chunk contribution is two MXU matmuls (decay-weighted r @ k^T,
then @ v) and the inter-chunk contribution is r @ state; the (K x V) state
is carried across the innermost sequential grid dimension in VMEM scratch.
Pairwise decays use exponent half-shifting for fp32 safety (same scheme as
the jnp path in models/rwkv.py — the two implementations cross-check).

Layout: r,k,v,logw (B, H, S, K) blocked (1,1,C,K); u (H, K) viewed as
(H, 1, K) and blocked (1,1,K), so a block's last two dims are the array's
(the TPU's 8x128 tiling rule); grid (B,H,NC).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def dot_f32(a, b, contract):
    """fp32 ``dot_general`` at full precision. Mosaic's default runs an
    fp32 matmul as one bf16 pass: on a v5e that left wkv6 at S=4096 off
    its fp32 oracle by 4e-3 of the output's scale."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def chunk_cumsum(x, chunk: int):
    """Inclusive cumsum over the rows of a (chunk, n) block, as one MXU
    matmul with a lower-triangular ones matrix: Mosaic has no cumsum."""
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return dot_f32((ii >= jj).astype(jnp.float32), x, ((1,), (0,)))


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_scr, *,
                 chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    f32 = jnp.float32
    rc = r_ref[0, 0].astype(f32)          # (C, K)
    kc = k_ref[0, 0].astype(f32)
    vc = v_ref[0, 0].astype(f32)
    lw = lw_ref[0, 0].astype(f32)         # log decay, <= 0
    u = u_ref[0].astype(f32)              # (1, K)

    cum = chunk_cumsum(lw, chunk)
    ce = cum - lw                         # exclusive cumsum
    tot = cum[-1:]                        # (1, K)

    state = state_scr[...]                # (K, V)
    # inter-chunk
    rd = rc * jnp.exp(ce)
    y = dot_f32(rd, state, ((1,), (0,)))
    # intra-chunk (strictly-lower pairs), half-shifted exponents
    rds = rc * jnp.exp(ce - 0.5 * tot)
    ki = kc * jnp.exp(0.5 * tot - cum)
    att = dot_f32(rds, ki, ((1,), (1,)))
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(ii > jj, att, 0.0)
    y = y + dot_f32(att, vc, ((1,), (0,)))
    # diagonal bonus term
    diag = jnp.sum(rc * kc * u, axis=1, keepdims=True)
    y = y + diag * vc
    # state update
    kdec = kc * jnp.exp(tot - cum)
    state_scr[...] = jnp.exp(tot).T * state + dot_f32(kdec, vc,
                                                      ((0,), (0,)))
    o_ref[0, 0] = y.astype(o_ref.dtype)


def wkv6_bhsk(r, k, v, logw, u, *, chunk: int = 128,
              interpret: bool = False):
    """r,k,v,logw: (B, H, S, K); u: (H, K). Returns y (B, H, S, K)."""
    b, h, s, dk = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    grid = (b, h, nc)
    spec = pl.BlockSpec((1, 1, chunk, dk),
                        lambda b_, h_, ci: (b_, h_, ci, 0))
    u_spec = pl.BlockSpec((1, 1, dk), lambda b_, h_, ci: (h_, 0, 0))
    return pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=chunk),
        grid=grid,
        in_specs=[spec, spec, spec, spec, u_spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, dk), r.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dk), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u.reshape(h, 1, dk))
