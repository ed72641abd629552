"""One new token's attention over its cache (``layers.decode_attention``,
the decode step's read path) gives the numbers of the block scan
(``layers.attention``) that prefill and training run; the Pallas decode
kernel that a TPU takes (``kernels.ops.paged_decode_attention``,
interpret mode here) gives those of ``decode_attention`` while reading
only the cache blocks up to the token's position, and ``layers`` takes it
only where it applies.

The kernel's tolerance is stated against bfloat16, the precision of its
operands and result: the largest error is at most two bf16 steps
(2 * 2**-7) of the largest magnitude of the reference's result.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
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


# the kernel's cases: a stacked cache of L layers over S positions, read
# in blocks of 128 (S is no multiple of 256)
L, S, BF16_STEP = 3, 384, 2.0 ** -7
EDGES = (0, 127, 128, 129, S - 1)


def _stacked(seed, batch, n_kv, group, dh):
    rng = np.random.default_rng(seed)
    # queries scaled up so that the scores spread and a cap of 30 bites
    q = rng.normal(size=(batch, 1, n_kv * group, dh)) * 4.0
    k = rng.normal(size=(L, batch, S, n_kv, dh))
    v = rng.normal(size=(L, batch, S, n_kv, dh))
    return tuple(jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))


def _kernel(spec, layer, is_global=True):
    return jax.jit(lambda q, k, v, pos: ops.paged_decode_attention(
        q, k, v, layer, pos, scale=spec.head_dim ** -0.5,
        window=spec.window, softcap=spec.softcap, is_global=is_global,
        interpret=True))


def _reference(spec, layer, is_global=True):
    return jax.jit(lambda q, k, v, pos: layers.decode_attention(
        q, k[layer], v[layer], spec, pos=pos, is_global=is_global))


def _close(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    err = np.abs(got - want).max()
    assert err <= 2 * BF16_STEP * np.abs(want).max(), err


@pytest.mark.parametrize("group,dh,batch", list(itertools.product(
    [1, 4, 16], [64, 128], [1, 3])))
def test_kernel_matches_decode_attention(group, dh, batch):
    """At the first position, at either side of a block's edge and at the
    cache's last position; heads narrower than 128 lanes (read with
    positions on lanes) and 128 wide (position-major); one layer of a
    stack, not the first."""
    n_kv = 2 if group < 16 else 1
    spec = layers.AttnSpec(n_kv * group, n_kv, dh)
    assert ops.decode_block(batch, S, n_kv, group, dh) == 128
    q, k, v = _stacked(group + dh + batch, batch, n_kv, group, dh)
    layer = 1 + batch % 2
    kernel, ref = _kernel(spec, layer), _reference(spec, layer)
    for pos in EDGES:
        pos = jnp.int32(pos)
        _close(kernel(q, k, v, pos), ref(q, k, v, pos))


@pytest.mark.parametrize("softcap,window,is_global", list(itertools.product(
    [0.0, 30.0], [0, 8], [True, False])))
def test_kernel_caps_and_windows_as_decode_attention(softcap, window,
                                                     is_global):
    """The soft cap, and the sliding window a local layer keeps (a global
    one ignores it), over blocks the window covers or leaves out."""
    spec = layers.AttnSpec(8, 2, 64, window=window, softcap=softcap)
    q, k, v = _stacked(int(softcap) + window, 3, 2, 4, 64)
    glob = jnp.asarray(is_global)
    kernel, ref = _kernel(spec, 0, glob), _reference(spec, 0, glob)
    for pos in (3, 130, S - 1):
        pos = jnp.int32(pos)
        _close(kernel(q, k, v, pos), ref(q, k, v, pos))


@pytest.mark.parametrize("dh", [64, 128])
def test_kernel_never_reads_past_the_live_block(dh):
    """Every position past the block that holds ``pos``, in every layer and
    row, holds NaN: the output is finite and equal to the reference's over
    the clean cache, so no dead block is read."""
    spec = layers.AttnSpec(8, 2, dh)
    q, k, v = _stacked(dh, 3, 2, 4, dh)
    pos = 140
    dead = jnp.arange(S)[None, None, :, None, None] >= 256
    k_nan, v_nan = (jnp.where(dead, jnp.nan, c) for c in (k, v))
    got = _kernel(spec, 2)(q, k_nan, v_nan, jnp.int32(pos))
    _close(got, _reference(spec, 2)(q, k, v, jnp.int32(pos)))


def _takes_kernel(f, *args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(f)(*args))


@pytest.mark.parametrize("case", [
    "decode", "unstacked", "one_device_mesh", "mesh", "length", "head_dim",
    "rows", "prompt_at_pos"])
def test_attn_block_takes_the_decode_kernel_only_where_it_applies(case):
    """One algorithm, chosen by what the call shows: one new token over a
    cache whose length is a multiple of a block, heads the kernel takes,
    and rows whose queries fit its VMEM, on one device.  Everything else
    keeps ``decode_attention`` (and off the TPU the kernel's branch is not
    lowered)."""
    from repro.sharding.context import use_mesh

    n_kv, group, dh, batch, max_len, tokens = 2, 4, 64, 3, S, 1
    if case == "length":
        max_len = S - 64
    elif case == "head_dim":
        dh = 36
    elif case == "rows":
        # 32 query heads of 64 over 8 KV heads: 512 rows' queries and
        # outputs exceed the kernel's VMEM for them
        n_kv, group, batch = 8, 4, 512
    elif case == "prompt_at_pos":
        tokens = 4
    spec = layers.AttnSpec(n_kv * group, n_kv, dh)
    d = 32
    params = layers.init_attn_params(jax.random.key(0), d, spec,
                                     jnp.bfloat16)
    x = jnp.ones((batch, tokens, d), jnp.bfloat16)
    shape = (L, batch, max_len, n_kv, dh)
    if case == "unstacked":
        shape = shape[1:]
    cache = (jnp.zeros(shape, jnp.bfloat16),) * 2
    layer = None if case == "unstacked" else jnp.int32(1)

    def f(x, cache, pos):
        return layers.attn_block(
            params, x, spec, rope_theta=1e4, norm_eps=1e-6,
            positions=pos + jnp.zeros((batch, tokens), jnp.int32),
            kv_cache=cache, cache_len=pos, layer=layer)[0]

    args = (x, cache, jnp.int32(5))
    want = case in ("decode", "unstacked", "one_device_mesh")
    if case in ("mesh", "one_device_mesh"):
        n = 2 if case == "mesh" else 1
        with use_mesh(jax.sharding.AbstractMesh((n, 1), ("data", "model"))):
            assert _takes_kernel(f, *args) is want
    else:
        assert _takes_kernel(f, *args) is want
    # off the TPU decode_attention runs
    out = jax.jit(f)(*args)
    assert out.shape == (batch, tokens, d)
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


def test_decode_block_adapts_to_the_shapes():
    """The largest block of 512, 256 or 128 positions that divides the
    cache and keeps one block of K under 256 KiB: granite-3-2b's 8 heads
    of 64 over serve-decode's 3072 positions and the hybrid's 2304 read
    256 at a time, glm4's 2 heads of 128 over 8192 read 512."""
    assert ops.decode_block(16, 3072, 8, 4, 64) == 256
    assert ops.decode_block(32, 2304, 8, 4, 64) == 256
    assert ops.decode_block(1, 8192, 2, 16, 128) == 512
    assert ops.decode_block(16, 3000, 8, 4, 64) == 0
