"""Weights of a dense decoder drawn from the run's seed.

The benchmark makes the weights itself, so that the reference can draw the
very same values again without taking anything from the program.  Every
leaf has its own key, folded from the seed, the leaf's name and, for a
layer's leaves, the layer's index: ``layer_weights(seed, i)`` gives layer
``i`` alone, equal to slice ``i`` of the stacked ``blocks`` that
``serving_weights`` hands the program.

Matrices are normal with standard deviation 1/sqrt(fan-in); norm gains are
0, since the program scales by (1 + gain).  The embedding's standard
deviation is the configuration's ``init.embed_std`` (see the configuration
files for why it is chosen).  Values are drawn in float32 and rounded to
bfloat16, the type the model is served in; the reference widens the same
bfloat16 values to float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.shapes import Sizes

LEAVES = ("wq", "wk", "wv", "wo", "wi_gate", "wi_up", "mlp_wo", "embed",
          "unembed")


def root_key(seed: int) -> jax.Array:
    """A key from any whole-number seed, also one wider than 32 bits."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0])), int(words[1]))


def _normal(key, name: str, shape, std, dtype) -> jax.Array:
    k = jax.random.fold_in(key, LEAVES.index(name))
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def _layer(key, s: Sizes, dtype) -> dict:
    d, hd = s.d, s.head_dim
    return {
        "ln1": jnp.zeros((d,), dtype),
        "attn": {
            "wq": _normal(key, "wq", (d, s.heads, hd), d ** -0.5, dtype),
            "wk": _normal(key, "wk", (d, s.kv_heads, hd), d ** -0.5, dtype),
            "wv": _normal(key, "wv", (d, s.kv_heads, hd), d ** -0.5, dtype),
            "wo": _normal(key, "wo", (s.heads, hd, d),
                          (s.heads * hd) ** -0.5, dtype),
        },
        "ln2": jnp.zeros((d,), dtype),
        "mlp": {
            "wi_gate": _normal(key, "wi_gate", (d, s.ff), d ** -0.5, dtype),
            "wi_up": _normal(key, "wi_up", (d, s.ff), d ** -0.5, dtype),
            "wo": _normal(key, "mlp_wo", (s.ff, d), s.ff ** -0.5, dtype),
        },
    }


def _layer_key(seed_key, i):
    return jax.random.fold_in(jax.random.fold_in(seed_key, 1), i)


def layer_weights(seed_key, s: Sizes, i, dtype=jnp.bfloat16) -> dict:
    """Layer ``i``'s weights (``i`` may be traced)."""
    return _layer(_layer_key(seed_key, i), s, dtype)


def head_weights(seed_key, s: Sizes, embed_std: float,
                 dtype=jnp.bfloat16) -> dict:
    """Embedding, final norm gain and, when untied, the output matrix."""
    key = jax.random.fold_in(seed_key, 0)
    out = {"embed": _normal(key, "embed", (s.vocab, s.d), embed_std, dtype),
           "final_norm": jnp.zeros((s.d,), dtype)}
    if not s.tied:
        out["unembed"] = _normal(key, "unembed", (s.d, s.vocab),
                                 s.d ** -0.5, dtype)
    return out


def serving_weights(seed_key, s: Sizes, embed_std: float,
                    dtype=jnp.bfloat16) -> dict:
    """The whole parameter tree in the program's layout (layers stacked on
    a leading axis).  Call under ``jax.jit`` to draw it on the device."""
    blocks = jax.vmap(lambda i: layer_weights(seed_key, s, i, dtype))(
        jnp.arange(s.layers))
    return {**head_weights(seed_key, s, embed_std, dtype), "blocks": blocks}
