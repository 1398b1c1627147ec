"""Pipeline parallelism (GPipe schedule) over a mesh "stage" axis.

Across pods the inter-pod ICI links are the slow dimension, so the right
parallelism across them is pipelining: each pod (or pod-slice) holds a
contiguous block of layers and microbatch activations flow stage-to-stage
via ``jax.lax.ppermute`` inside ``shard_map``.

``pipeline_apply`` runs the canonical schedule: with S stages and M
microbatch slices, T = M + S - 1 ticks; stage s computes microbatch t-s at tick
t; activations hop one stage per tick (bubble fraction (S-1)/T). The layer
stack must be expressible as S identical-signature stage functions over
stacked per-stage params — exactly the shape of our scan-over-layers
models.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.sharding.mesh import auto_axes


def pipeline_apply(stage_fn: Callable, stage_params, x, *, mesh: Mesh,
                   axis: str = "stage", n_microbatches: int):
    """Run x through S pipelined stages.

    stage_fn(params_slice, activation) -> activation; stage_params: pytree
    stacked on a leading S dim (sharded P(axis, ...)); x: (batch, ...)
    with batch % n_microbatches == 0. Returns stage_fn applied S times.
    """
    s = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_microbatches == 0
    mb = b // n_microbatches
    micro = x.reshape((n_microbatches, mb) + x.shape[1:])

    def body(params_local, micro_local):
        # params_local: (1, ...) this stage's slice; micro_local: the full
        # microbatch stream (replicated across stages)
        params_here = jax.tree.map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(axis)
        ticks = n_microbatches + s - 1

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (when in range)
            feed = micro_local[jnp.minimum(t, n_microbatches - 1)]
            cur = jnp.where(sid == 0, feed, buf)
            y = stage_fn(params_here, cur)
            # last stage commits its result for microbatch t-(S-1)
            out_idx = t - (s - 1)
            commit = (sid == s - 1) & (out_idx >= 0)
            outs = jax.lax.cond(
                commit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), 0),
                lambda o: o, outs)
            # hop: stage i -> i+1 (ring permute; the wraparound value into
            # stage 0 is ignored — stage 0 always reads the feed)
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % s) for i in range(s)])
            return (nxt, outs), None

        buf0 = jnp.zeros_like(micro_local[0])
        outs0 = jnp.zeros_like(micro_local)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(ticks))
        # every stage returns outs; only the last stage's is real — take it
        # via a psum of masked values (others contribute zeros)
        outs = jnp.where(sid == s - 1, outs, 0)
        return jax.lax.psum(outs, axis)

    out = jax.shard_map(
        body, mesh=auto_axes(mesh),
        in_specs=(P(axis), P()),      # params stage-sharded, micro replicated
        out_specs=P(),
        check_vma=False,
    )(stage_params, micro)
    return out.reshape((b,) + out.shape[2:])


def sequential_apply(stage_fn: Callable, stage_params, x):
    """Reference: the same stages applied serially (oracle for tests)."""
    def body(carry, p):
        return stage_fn(p, carry), None
    y, _ = jax.lax.scan(body, x, stage_params)
    return y
