"""Collective matmuls: all-gather-matmul and matmul-reduce-scatter rings.

These are the Shared-PIM-style replacements for XLA's blocking collectives
around tensor-parallel einsums (the "LISA analogue", DESIGN.md Sec 3):

* ``ag_matmul``:   Y = X @ W with X sequence-sharded and W column-sharded.
  Baseline XLA: all-gather X (everyone stalls), then matmul.  Here: ring the
  X chunks; each step matmuls the resident chunk while the next chunk is in
  flight on the bus.
* ``matmul_rs``:   Y = X @ W with W row-sharded, output sequence-sharded.
  Baseline: full partial-sum matmul, then blocking reduce-scatter.  Here:
  the partial sums ride the ring, accumulating chunk-by-chunk behind the
  per-chunk matmuls.

All functions are shard_map bodies; ``ops`` wraps them with mesh plumbing.
Numerics are exact (modulo float reassociation in matmul_rs) and tested
against the unsharded einsum on 8 host devices.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.overlap import sharedbus


def ag_matmul_body(x: jax.Array, w: jax.Array, axis_name: str) -> jax.Array:
    """shard_map body.  x: (B, T/n, D) local; w: (D, F/n) local.

    Returns (B, T, F/n): the all-gathered-dim output, computed chunk-by-chunk
    while chunks circulate (overlap of ICI with MXU).
    """
    n = lax.axis_size(axis_name)
    B, t, D = x.shape
    F = w.shape[1]
    out0 = jnp.zeros((n, B, t, F), x.dtype)

    def consume(acc, chunk, src):
        y = jnp.einsum("btd,df->btf", chunk, w)
        return lax.dynamic_update_index_in_dim(acc, y, src, 0)

    out = sharedbus.stream_ring(x, axis_name, consume, out0)
    return out.transpose(1, 0, 2, 3).reshape(B, n * t, F)


def matmul_rs_body(x: jax.Array, w: jax.Array, axis_name: str) -> jax.Array:
    """shard_map body.  x: (B, T, F/n) local; w: (F/n, D) local.

    Returns (B, T/n, D): reduce-scattered over T.  Step i: compute the
    partial product for the chunk that is i hops ahead, add the incoming
    partial sums, hand the accumulator to the neighbor ("transmit shared
    row") while the next partial product is computed.
    """
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    B, T, f = x.shape
    D = w.shape[1]
    t = T // n
    perm = sharedbus.ring_perm(axis_name, 1)

    def body(i, acc):
        # the accumulator arriving at this step represents chunk
        # (me - 1 - i); after n steps it sits at its home rank (= chunk me)
        idx = (me + n - 1 - i) % n
        xc = lax.dynamic_slice(x, (0, idx * t, 0), (B, t, f))
        part = jnp.einsum("btf,fd->btd", xc, w)
        acc = acc + part
        return jax.lax.cond(
            i < n - 1, lambda a: lax.ppermute(a, axis_name, perm),
            lambda a: a, acc)

    acc = lax.pcast(jnp.zeros((B, t, D), x.dtype), axis_name, to="varying")
    return lax.fori_loop(0, n, body, acc)


def ag_matmul(x: jax.Array, w: jax.Array, mesh: Mesh,
              axis_name: str = "model") -> jax.Array:
    """Y[B,T,F] = X[B,T,D] @ W[D,F], X seq-sharded / W col-sharded on axis."""
    fn = jax.shard_map(
        functools.partial(ag_matmul_body, axis_name=axis_name), mesh=mesh,
        in_specs=(P(None, axis_name, None), P(None, axis_name)),
        out_specs=P(None, None, axis_name))
    return fn(x, w)


def matmul_rs(x: jax.Array, w: jax.Array, mesh: Mesh,
              axis_name: str = "model") -> jax.Array:
    """Y[B,T/n,D] = reduce_scatter_T(X[B,T,F] @ W[F,D]) with F sharded."""
    fn = jax.shard_map(
        functools.partial(matmul_rs_body, axis_name=axis_name), mesh=mesh,
        in_specs=(P(None, None, axis_name), P(axis_name, None)),
        out_specs=P(None, axis_name, None))
    return fn(x, w)


def overlapped_ffn(x: jax.Array, wi_gate: jax.Array, wi_up: jax.Array,
                   wo: jax.Array, mesh: Mesh, act, axis_name: str = "model"
                   ) -> jax.Array:
    """Full Shared-PIM-style TP FFN: AG-matmul in, matmul-RS out.

    x arrives sequence-sharded (B, T, D) with T sharded on ``axis_name``;
    returns the same layout.  The two blocking collectives of the baseline
    (all-gather before, reduce-scatter after) become rings overlapped with
    the two matmuls.
    """
    g = ag_matmul(x, wi_gate, mesh, axis_name)
    u = ag_matmul(x, wi_up, mesh, axis_name)
    h = act(g) * u
    return matmul_rs(h, wo, mesh, axis_name)
