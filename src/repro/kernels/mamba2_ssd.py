"""Mamba-2 SSD chunked scan — TPU Pallas kernel.

Hardware adaptation (DESIGN.md §3): the Triton SSD kernel uses warp-level
semiring scans; the TPU version uses the block matrix form — per chunk the
intra-chunk term is (C_t · B_j decay-weighted) masked-matmul on the MXU and
the (N x P) state carries across the innermost grid dim in VMEM scratch.
Scalar-per-head decay makes the exponent algebra 1-D (cheaper than WKV6's
per-channel decay).

Layouts: x (B,H,S,P) blocked (1,1,C,P); dt (B,H,S) viewed as (B,H,S,1)
and blocked (1,1,C,1), a column whose last dim is the array's (the TPU's
8x128 tiling rule); Bmat/Cmat (B,G,S,N) blocked (1,1,C,N) with
head->group index mapping; A,D (H,) whole in SMEM, read per head.
Grid (B, H, NC).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rwkv6 import chunk_cumsum, dot_f32


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, o_ref,
                state_scr, *, chunk: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    f32 = jnp.float32
    x = x_ref[0, 0].astype(f32)           # (C, P)
    dt = dt_ref[0, 0].astype(f32)         # (C, 1)
    a = a_ref[hi].astype(f32)             # scalar <0
    bm = b_ref[0, 0].astype(f32)          # (C, N)
    cm = c_ref[0, 0].astype(f32)          # (C, N)
    dcoef = d_ref[hi].astype(f32)

    la = dt * a                           # (C, 1) log decay per token
    cum = chunk_cumsum(la, chunk)         # inclusive
    tot = jnp.sum(la)                     # scalar: total chunk decay
    xd = x * dt                           # dt-weighted input

    state = state_scr[...]                # (N, P)
    # inter-chunk: y_t += C_t exp(cum_t) . state
    cdec = cm * jnp.exp(cum)
    y = dot_f32(cdec, state, ((1,), (0,)))
    # intra-chunk pairs j <= t (half-shifted exponents)
    cs = cm * jnp.exp(cum - 0.5 * tot)
    bs = bm * jnp.exp(0.5 * tot - cum)
    att = dot_f32(cs, bs, ((1,), (1,)))
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(ii >= jj, att, 0.0)
    y = y + dot_f32(att, xd, ((1,), (0,)))
    # state update: h' = exp(tot) h + sum_j exp(tot - cum_j) B_j xd_j^T
    bdec = bm * jnp.exp(tot - cum)
    state_scr[...] = jnp.exp(tot) * state + dot_f32(bdec, xd, ((0,), (0,)))
    # skip connection
    y = y + x * dcoef
    o_ref[0, 0] = y.astype(o_ref.dtype)


def ssd_bhsp(x, dt, A, Bm, Cm, D, *, chunk: int = 128,
             interpret: bool = False):
    """x: (B,H,S,P); dt: (B,H,S); A,D: (H,); Bm,Cm: (B,G,S,N)."""
    b, h, s, p_ = x.shape
    g, n = Bm.shape[1], Bm.shape[3]
    reps = h // g
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    grid = (b, h, nc)
    xspec = pl.BlockSpec((1, 1, chunk, p_),
                         lambda b_, h_, ci: (b_, h_, ci, 0))
    dtspec = pl.BlockSpec((1, 1, chunk, 1),
                          lambda b_, h_, ci: (b_, h_, ci, 0))
    hspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    bcspec = pl.BlockSpec((1, 1, chunk, n),
                          lambda b_, h_, ci: (b_, h_ // reps, ci, 0))
    return pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=grid,
        in_specs=[xspec, dtspec, hspec, bcspec, bcspec, hspec],
        out_specs=xspec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, p_), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p_), jnp.float32)],
        interpret=interpret,
    )(x, dt[..., None], A, Bm, Cm, D)
