"""GPipe-style pipeline parallelism over a mesh axis (shard_map).

The layer stack's repeat axis shards over the pipeline axis (each stage
holds R/n_stages super-blocks); microbatches flow through stages with
``ppermute`` at the boundaries. Total ticks = M + n_stages − 1; the
bubble fraction is (n−1)/(M+n−1).

Scope: forward/inference pipelining (the diffusion sampler's score-net
forward is the motivating workload — one Algorithm-1 iteration is two
pipelined forwards). The machinery is generic over any
``body(stage_params, x) → x`` with x-shaped carry.

Degenerate single-stage (axis size 1) is exactly a scan — that is the
CPU-testable path; multi-stage correctness is compile-proven by the
dry-run variant and structurally by construction (each microbatch
visits every stage once, in order).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

Array = jax.Array


def _pipeline_local(params_local, x_mb: Array, *, body: Callable,
                    axis: str, num_microbatches: int):
    """Per-stage body (inside shard_map).

    params_local: stage's slice of the stacked weights (R_local, ...).
    x_mb: (M, mb, ...) microbatches — input on stage 0, ignored elsewhere.
    Returns (M, mb, ...) outputs — valid on the LAST stage.
    """
    n = jax.lax.axis_size(axis)
    stage = jax.lax.axis_index(axis)
    M = num_microbatches
    ticks = M + n - 1

    mb_shape = x_mb.shape[1:]
    zeros = jnp.zeros(mb_shape, x_mb.dtype)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def tick_fn(carry, t):
        in_buf, outputs = carry
        # stage 0 feeds microbatch t (while available); others take the
        # activation handed over by the previous stage last tick.
        mb_idx = jnp.clip(t, 0, M - 1)
        feed = jax.lax.dynamic_index_in_dim(x_mb, mb_idx, keepdims=False)
        x_in = jnp.where(stage == 0, feed, in_buf)

        y = body(params_local, x_in)

        # hand over to the next stage (ring; stage n-1 → 0 is ignored)
        in_buf_next = jax.lax.ppermute(y, axis, perm)

        # last stage emits microbatch (t - (n-1)) at tick t
        out_idx = jnp.clip(t - (n - 1), 0, M - 1)
        is_valid = jnp.logical_and(stage == n - 1, t >= n - 1)
        updated = jax.lax.dynamic_update_index_in_dim(outputs, y, out_idx,
                                                      axis=0)
        outputs = jnp.where(is_valid, updated, outputs)
        return (in_buf_next, outputs), None

    init = (
        jax.lax.pcast(zeros, (axis,), to="varying"),
        jax.lax.pcast(jnp.zeros_like(x_mb), (axis,), to="varying"),
    )
    (_, outputs), _ = jax.lax.scan(tick_fn, init, jnp.arange(ticks))
    # broadcast the last stage's outputs to every stage (tiny psum trick:
    # zero elsewhere, sum over the axis)
    outputs = jnp.where(stage == n - 1, outputs, jnp.zeros_like(outputs))
    return jax.lax.psum(outputs, axis)


def pipeline_forward(
    params_stacked,       # pytree, leaves (R, ...) — R % axis_size == 0
    x: Array,             # (B, ...) global batch
    body: Callable,       # (stage_params, x) → x, applied per super-block
    *,
    axis: str = "pod",
    num_microbatches: int = 4,
    mesh=None,
) -> Array:
    """Run ``body`` over the full stacked depth, pipelined over ``axis``.

    The weights' repeat axis is sharded over ``axis`` (stage-local
    scan inside ``body`` handles the R_local super-blocks); activations
    stream through stages in microbatches.
    """
    B = x.shape[0]
    assert B % num_microbatches == 0, (B, num_microbatches)
    x_mb = x.reshape((num_microbatches, B // num_microbatches) + x.shape[1:])

    # mesh=None: jax.shard_map takes the mesh set by ``jax.set_mesh``
    fn = jax.shard_map(
        functools.partial(
            _pipeline_local, body=body, axis=axis,
            num_microbatches=num_microbatches,
        ),
        in_specs=(P(axis), P()),   # weights stage-sharded; x replicated
        out_specs=P(),             # outputs replicated (psum-broadcast)
        axis_names={axis},
        mesh=mesh,
    )
    out = fn(params_stacked, x_mb)
    return out.reshape((B,) + out.shape[2:])
