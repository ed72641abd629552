"""Weights drawn from the seed: the reference's layer-by-layer draw equals
the program's stacked draw."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import shapes, weights
from benchmarks.chip.tests.cells import cut


def test_layer_draw_equals_stacked_draw():
    cell = cut("glm4-9b-20L.serve-prefill")
    s = cell.sizes
    key = weights.root_key(2 ** 31 + 5)
    whole = jax.jit(lambda k: weights.serving_weights(k, s, 0.02))(key)
    for i in range(s.layers):
        one = weights.layer_weights(key, s, i)
        for a, b in zip(jax.tree.leaves(one),
                        jax.tree.leaves(jax.tree.map(lambda x: x[i],
                                                     whole["blocks"]))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    head = weights.head_weights(key, s, 0.02)
    np.testing.assert_array_equal(np.asarray(head["unembed"]),
                                  np.asarray(whole["unembed"]))
    assert whole["embed"].dtype == jnp.bfloat16


def test_seeds_differ_and_large_seeds_work():
    s = shapes.Sizes(d=8, layers=1, heads=2, kv_heads=1, head_dim=4, ff=16,
                     vocab=32, tied=True, rope_theta=1e4, norm_eps=1e-6)
    draws = [weights.head_weights(weights.root_key(seed), s, 0.02)["embed"]
             for seed in (1, 2, 2 ** 31 + 1, 2 ** 63)]
    for a, b in zip(draws, draws[1:]):
        assert not np.array_equal(np.asarray(a), np.asarray(b))
