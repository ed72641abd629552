"""SharedBus: the Shared-PIM staging-row abstraction on a TPU ring.

The paper's mechanism (DESIGN.md Sec 3): two *shared rows* per subarray — one
transmitting while one receives — let the BK-bus move data concurrently with
subarray compute.  On a TPU mesh axis the exact analogue is a double-buffered
``lax.ppermute`` ring: at step *i* the chip computes on the resident buffer
("the row being consumed") while the alternate buffer ("the receiving shared
row") is being filled by the neighbor over ICI.  XLA schedules
`collective-permute` asynchronously against MXU work, so the transfer cost is
max(compute, transfer), not the sum — the paper's STALL -> NOP transformation.

These helpers are written for use INSIDE ``jax.shard_map`` bodies.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def ring_perm(axis_name: str, shift: int = 1) -> list[tuple[int, int]]:
    n = lax.axis_size(axis_name)
    return [(i, (i + shift) % n) for i in range(n)]


def stream_ring(x: jax.Array, axis_name: str,
                consume: Callable[[jax.Array, jax.Array, jax.Array], jax.Array],
                init, *, reverse: bool = False):
    """Run ``consume(carry, chunk, src_index)`` over every ring-neighbor chunk.

    ``x`` is this chip's resident chunk.  Each of the n steps overlaps the
    ppermute of the *next* chunk (into the receiving "shared row") with the
    ``consume`` of the current one — the Shared-PIM pipeline in Fig 4.
    Returns the final carry.
    """
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    shift = -1 if reverse else 1
    perm = ring_perm(axis_name, shift)
    # mark the carry as device-varying on the ring axis (shard_map vma typing)
    init = jax.tree.map(lambda a: lax.pcast(a, axis_name, to="varying"),
                        init)

    def step(i, state):
        carry, buf = state
        # after i hops of +shift, the resident chunk originated at me - i*shift
        src = (me - i * shift) % n
        # launch the transfer of the NEXT chunk (fills the receiving row)
        nxt = lax.ppermute(buf, axis_name, perm)
        # ... while consuming the resident chunk (compute proceeds: NOP, not
        # STALL — XLA overlaps collective-permute with the consume compute)
        carry = consume(carry, buf, src)
        return carry, nxt

    carry, _ = lax.fori_loop(0, n, step, (init, x))
    return carry


def bidirectional_stream(x: jax.Array, axis_name: str,
                         consume: Callable, init):
    """Split-ring variant: half the chunks flow clockwise, half counter-
    clockwise (doubling effective link bandwidth, like the paper's segmented
    BK-bus operating its segments in parallel)."""
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    fwd = ring_perm(axis_name, 1)
    bwd = ring_perm(axis_name, -1)
    half = x.shape[0] // 2
    buf_f, buf_b = x[:half], x[half:]

    def step(i, state):
        carry, bf, bb = state
        nf = lax.ppermute(bf, axis_name, fwd)
        nb = lax.ppermute(bb, axis_name, bwd)
        src_f = (me - i) % n
        src_b = (me + i) % n
        carry = consume(carry, jnp.concatenate([bf, bb], axis=0),
                        (src_f, src_b))
        return carry, nf, nb

    carry, _, _ = lax.fori_loop(0, n, step, (init, buf_f, buf_b))
    return carry
