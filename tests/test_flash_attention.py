"""The causal flash kernel that prefill and training take on a TPU
(``kernels.ops.causal_flash_attention``, interpret mode here) gives the
numbers of the block scan (``layers.attention`` off the TPU), forward and
backward, and ``layers.attention`` takes it only where it applies.

Tolerances are stated against bfloat16, the precision of every operand
and result: the largest error of an output or a gradient is at most two
bf16 steps (2 * 2**-7) of the largest magnitude of the scan's result.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.models import layers
from repro.models.layers import AttnSpec

BF16_STEP = 2.0 ** -7
B, K = 1, 2


def _close(got, want, steps=2):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.abs(got - want).max()
    assert err <= steps * BF16_STEP * np.abs(want).max(), err


def _qkv(seed, T, G, D, n_keys=None):
    rng = np.random.default_rng(seed)
    n_keys = n_keys or T
    # queries scaled up so that the scores spread and a cap of 50 bites
    q = rng.normal(size=(B, T, K * G, D)) * 4.0
    k = rng.normal(size=(B, n_keys, K, D))
    v = rng.normal(size=(B, n_keys, K, D))
    return tuple(jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))


def _scan(spec, **kw):
    return lambda q, k, v: layers._block_scan(
        q, k, v, spec=spec, **{"q_offset": 0, "is_global": True,
                               "kv_len": None, **kw})


@pytest.mark.parametrize("D,G,T,softcap", list(itertools.product(
    [64, 128], [1, 4, 16], [128, 256, 384], [0.0, 50.0])))
def test_kernel_matches_block_scan(D, G, T, softcap):
    """Output and the gradients in q, k and v, against the float32 block
    scan over 128-key blocks."""
    spec = AttnSpec(K * G, K, D, softcap=softcap, kv_block=128)
    q, k, v = _qkv(D + G + T, T, G, D)
    w = jnp.asarray(np.random.default_rng(T).normal(size=q.shape),
                    jnp.float32)
    kernel = lambda q, k, v: ops.causal_flash_attention(
        q, k, v, scale=D ** -0.5, softcap=softcap, interpret=True)
    scan = _scan(spec)
    _close(jax.jit(kernel)(q, k, v), jax.jit(scan)(q, k, v))

    def grads(f):
        loss = lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * w)
        return jax.jit(jax.grad(loss, (0, 1, 2)))(q, k, v)

    for got, want in zip(grads(kernel), grads(scan)):
        _close(got, want)


def test_prompt_over_its_own_keys_matches_the_cache_scan():
    """Prefill's kernel path, over the prompt's fresh K/V alone, gives what
    the scan over a longer cache gives with everything past the prompt
    masked (``kv_len`` = T), whatever those positions hold."""
    T, G, D, S = 256, 4, 64, 640
    spec = AttnSpec(K * G, K, D, kv_block=128)
    q, ck, cv = _qkv(7, T, G, D, n_keys=S)
    got = ops.causal_flash_attention(q, ck[:, :T], cv[:, :T],
                                     scale=D ** -0.5, interpret=True)
    want = _scan(spec, kv_len=T)(q, ck, cv)
    _close(got, want)


def _takes_kernel(f, *args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(f)(*args))


@pytest.mark.parametrize("case", [
    "self_attention", "prompt_from_zero", "decode", "prompt_at_pos",
    "cross", "traced_window", "static_window", "length", "mesh"])
def test_attention_takes_the_kernel_only_where_it_applies(case):
    """One algorithm, chosen by what the call shows: several queries over
    their own keys from a static position 0, no traced window flag, a
    length that is a multiple of 128, one device.  Everything else keeps
    the block scan (and off the TPU the kernel's branch is not lowered)."""
    from repro.sharding.context import use_mesh

    T, G, D = 256, 4, 64
    q, k, v = _qkv(0, T, G, D)
    spec = AttnSpec(K * G, K, D, kv_block=128)
    window = AttnSpec(K * G, K, D, window=64, kv_block=128)
    att = layers.attention
    want, f, args = {
        "self_attention": (True, lambda q, k, v: att(q, k, v, spec),
                           (q, k, v)),
        "prompt_from_zero": (True, lambda q, k, v: att(
            q, k, v, spec, q_offset=0), (q, k, v)),
        "decode": (False, lambda q, k, v: att(q[:, -1:], k, v, spec,
                                              q_offset=T - 1), (q, k, v)),
        "prompt_at_pos": (False, lambda q, k, v, p: att(
            q, k, v, spec, q_offset=p, kv_len=p + T),
            (q, k, v, jnp.int32(0))),
        "cross": (False, lambda q, k, v: att(q, k, v, spec, q_offset=T),
                  (q, k, v)),
        "traced_window": (False, lambda q, k, v, g: att(
            q, k, v, window, is_global=g), (q, k, v, jnp.bool_(True))),
        "static_window": (True, lambda q, k, v: att(
            q, k, v, window, is_global=True), (q, k, v)),
        "length": (False, lambda q, k, v: att(
            q[:, :T - 64], k[:, :T - 64], v[:, :T - 64], spec), (q, k, v)),
        "mesh": (False, lambda q, k, v: att(q, k, v, spec), (q, k, v)),
    }[case]
    if case == "mesh":
        # two devices, as the train launcher's data-parallel mesh holds
        with use_mesh(jax.sharding.AbstractMesh((2, 1), ("data", "model"))):
            assert _takes_kernel(f, *args) is want
    else:
        assert _takes_kernel(f, *args) is want
    # off the TPU the scan runs
    out = jax.jit(f)(*args)
    assert out.shape[1] in (1, T, T - 64) and bool(jnp.isfinite(
        out.astype(jnp.float32)).all())
