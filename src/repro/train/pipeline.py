"""Pipeline parallelism with Shared-PIM-style stage hand-off.

Stages are laid out along a mesh axis; each microbatch's activations move
stage -> stage over ``lax.ppermute`` — the same double-buffered "shared row"
hand-off as ``core/overlap`` (one buffer streams to the next stage while the
stage computes the next microbatch: Fig 4's pipelining, at pipeline scale).

This is the GPipe-style schedule expressed as a shard_map: with S stages and
M microbatches the loop runs S+M-1 ticks; at tick t, stage s computes
microbatch t-s (when in range).  Bubbles are the usual (S-1)/(S+M-1)
fraction; the transfer itself is overlapped by XLA (collective-permute is
async against the stage's compute on the next tick's resident microbatch).

``pipeline()`` is deliberately model-agnostic: it takes a per-stage apply
function ``f(stage_params, x) -> x``; models expose per-stage parameter
stacks by reshaping their scanned layer stacks to (n_stages, layers_per
stage, ...).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _stage_body(stage_params, xs, f, axis_name: str, n_micro: int):
    """shard_map body: xs (n_micro, mb, ...) input microbatches (only stage
    0's copy is consumed).  Returns stacked outputs (only stage S-1's copy
    is meaningful)."""
    n_stages = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    # shard_map keeps the (now size-1) stage dim on the params; drop it
    stage_params = jax.tree.map(lambda a: a[0], stage_params)
    mb_shape = xs.shape[1:]

    outs0 = jnp.zeros_like(xs)
    buf0 = jnp.zeros(mb_shape, xs.dtype)
    ticks = n_stages + n_micro - 1

    def tick(t, state):
        buf, outs = state
        mb_idx = t - me                       # microbatch this stage works on
        active = (mb_idx >= 0) & (mb_idx < n_micro)
        # stage 0 pulls a fresh microbatch from the host stream; others use
        # the activations that arrived over the "bus" last tick
        x_in = jnp.where(
            me == 0,
            lax.dynamic_index_in_dim(xs, jnp.clip(mb_idx, 0, n_micro - 1),
                                     keepdims=False),
            buf)
        y = f(stage_params, x_in)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # last stage records its finished microbatch
        outs = jnp.where(
            (me == n_stages - 1) & active,
            lax.dynamic_update_index_in_dim(
                outs, y, jnp.clip(mb_idx, 0, n_micro - 1), 0),
            outs)
        # hand the activations to the next stage ("transmit shared row"),
        # while the next tick's compute proceeds on the other buffer
        buf = lax.ppermute(y, axis_name, fwd)
        return buf, outs

    buf0 = lax.pcast(buf0, axis_name, to="varying")
    outs0 = lax.pcast(outs0, axis_name, to="varying")
    _, outs = lax.fori_loop(0, ticks, tick, (buf0, outs0))
    return outs


def pipeline(f, stage_params, xs: jax.Array, mesh: Mesh,
             axis_name: str = "pipe") -> jax.Array:
    """Run ``f`` as a pipeline over ``axis_name``.

    stage_params: pytree whose leaves have leading dim n_stages (sharded on
    the pipe axis).  xs: (n_micro, mb, ...) microbatched inputs (replicated).
    Returns (n_micro, mb, ...) outputs of the final stage.
    """
    n_micro = xs.shape[0]
    body = functools.partial(_stage_body, f=f, axis_name=axis_name,
                             n_micro=n_micro)

    def reduce_out(stage_params, xs):
        outs = body(stage_params, xs)
        n_stages = lax.axis_size(axis_name)
        me = lax.axis_index(axis_name)
        # only the last stage holds real outputs; psum broadcasts them
        outs = jnp.where(me == n_stages - 1, outs, jnp.zeros_like(outs))
        return lax.psum(outs, axis_name)

    fn = jax.shard_map(
        reduce_out, mesh=mesh,
        in_specs=(P(axis_name), P()), out_specs=P())
    return fn(stage_params, xs)
