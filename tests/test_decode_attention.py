"""One new token's attention over its cache (``layers.decode_attention``,
the decode step's read path) gives the numbers of the block scan
(``layers.attention``) that prefill and training run."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers

B, H, K, DH, MAX_LEN, BLOCK = 2, 4, 2, 16, 64, 16


@pytest.mark.parametrize(
    "window,is_global,softcap,length",
    list(itertools.product([0, 8], [True, False], [0.0, 30.0],
                           [1, BLOCK, BLOCK + 1, MAX_LEN - 1])))
def test_decode_attention_matches_block_scan(window, is_global, softcap,
                                             length):
    """``length`` is the cache's length with the new token, which sits at
    position ``length - 1``; the cache holds noise beyond it, which both
    paths must mask."""
    spec = layers.AttnSpec(H, K, DH, window=window, softcap=softcap,
                           kv_block=BLOCK)
    kq, kk, kv = jax.random.split(jax.random.key(length), 3)
    q = jax.random.normal(kq, (B, 1, H, DH), jnp.float32)
    k = 3.0 * jax.random.normal(kk, (B, MAX_LEN, K, DH), jnp.float32)
    v = jax.random.normal(kv, (B, MAX_LEN, K, DH), jnp.float32)
    pos = jnp.asarray(length - 1, jnp.int32)
    glob = jnp.asarray(is_global)
    got = jax.jit(lambda q, k, v, pos: layers.decode_attention(
        q, k, v, spec, pos=pos, is_global=glob))(q, k, v, pos)
    want = layers.attention(q, k, v, spec, q_offset=pos, is_global=glob,
                            kv_len=pos + 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
