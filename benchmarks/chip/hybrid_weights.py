"""Weights of a layer-pattern hybrid (granite-4.0-h) drawn from the run's
seed, in the program's layout: ``blocks`` stacks the mamba layers in layer
order, ``attn_blocks`` the attention layers.

As in ``weights.py`` every leaf has its own key, folded from the seed, the
leaf's name and the layer's index in the whole stack, so that
``mamba_layer_weights(key, s, i)`` draws layer ``i`` alone, equal to its
slice of ``blocks``, and the reference can draw it again.  An attention
layer and the embedding are drawn by ``weights.py`` itself (attention
layer ``i`` as dense layer ``i``).

A mamba layer's matrices are normal with standard deviation 1/sqrt(fan-in)
and its norm gains 0 (the program scales by 1 + gain).  The rest follows
the published Mamba-2 initialization, given with reasons under ``init`` in
the configuration file: conv weights and bias uniform within
+-1/sqrt(conv) (PyTorch's default for a depthwise Conv1d), A_log = log of
uniform [1, 16] per head, dt_bias = inverse softplus of dt log-uniform in
[0.001, 0.1], D = 1.  Matrices are drawn in float32 and rounded to
bfloat16; A_log, dt_bias and D stay float32, as the program keeps them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip import weights
from benchmarks.chip.hybrid_shapes import HybridSizes

LEAVES = ("in_proj", "conv_w", "conv_b", "A_log", "dt", "out_proj",
          "wi_gate", "wi_up", "mlp_wo")
DT_MIN, DT_MAX, A_MIN, A_MAX = 1e-3, 1e-1, 1.0, 16.0


def _key(key, name: str):
    return jax.random.fold_in(key, LEAVES.index(name))


def _normal(key, name, shape, std, dtype):
    return (jax.random.normal(_key(key, name), shape, jnp.float32)
            * std).astype(dtype)


def _uniform(key, name, shape, lo, hi):
    return jax.random.uniform(_key(key, name), shape, jnp.float32, lo, hi)


def mamba_layer_weights(seed_key, s: HybridSizes, i,
                        dtype=jnp.bfloat16) -> dict:
    """Mamba layer ``i`` (its index among all layers; may be traced)."""
    key = jax.random.fold_in(jax.random.fold_in(seed_key, 2), i)
    d, di, H = s.d, s.d_inner, s.mamba_heads
    bound = s.conv ** -0.5
    dt = jnp.exp(_uniform(key, "dt", (H,), jnp.log(DT_MIN),
                          jnp.log(DT_MAX)))
    return {
        "ln": jnp.zeros((d,), dtype),
        "mixer": {
            "in_proj": _normal(key, "in_proj", (d, s.in_proj_width),
                               d ** -0.5, dtype),
            "conv_w": _uniform(key, "conv_w", (s.conv, s.conv_dim), -bound,
                               bound).astype(dtype),
            "conv_b": _uniform(key, "conv_b", (s.conv_dim,), -bound,
                               bound).astype(dtype),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),   # softplus^-1(dt)
            "A_log": jnp.log(_uniform(key, "A_log", (H,), A_MIN, A_MAX)),
            "D": jnp.ones((H,), jnp.float32),
            "norm_w": jnp.zeros((di,), dtype),
            "out_proj": _normal(key, "out_proj", (di, d), di ** -0.5,
                                dtype),
        },
        "ln2": jnp.zeros((d,), dtype),
        "mlp": {
            "wi_gate": _normal(key, "wi_gate", (d, s.ff), d ** -0.5, dtype),
            "wi_up": _normal(key, "wi_up", (d, s.ff), d ** -0.5, dtype),
            "wo": _normal(key, "mlp_wo", (s.ff, d), s.ff ** -0.5, dtype),
        },
    }


def serving_weights(seed_key, s: HybridSizes, embed_std: float,
                    dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree in the program's layout.  Call under
    ``jax.jit`` to draw it on the device."""
    def stack(kind, draw):
        idx = [i for i, t in enumerate(s.layer_types) if t == kind]
        return jax.vmap(lambda i: draw(seed_key, s, i, dtype))(
            jnp.asarray(idx))

    return {**weights.head_weights(seed_key, s, embed_std, dtype),
            "blocks": stack("mamba", mamba_layer_weights),
            "attn_blocks": stack("attention", weights.layer_weights)}
