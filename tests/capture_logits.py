"""Capture every architecture's logits for the model parity test.

Each registry architecture at its reduced config, in float32, with the
parameters of a fixed seed: the logits of ``forward`` over a prompt, of
``prefill`` over it, and of three ``decode_step`` calls after it::

    PYTHONPATH=src python tests/capture_logits.py

and commit the resulting ``tests/golden_logits.npz``.
``tests/test_model_parity.py`` holds the model to these numbers, so a
change to how the model walks its layer stacks can be checked to keep
every family's results.
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.models import model as model_lib

GOLDEN_PATH = Path(__file__).parent / "golden_logits.npz"

#: prompts and tokens decoded after them
BATCH, PROMPT, DECODE = 2, 12, 3


def logits(arch: str) -> dict[str, np.ndarray]:
    """``arch``'s forward, prefill and decode logits (float32)."""
    cfg = dataclasses.replace(registry.get(arch).reduced(), dtype="float32")
    m = model_lib.build(cfg)
    params = m.init(jax.random.key(0))
    if "cross_blocks" in params:
        # cross-attention gates start shut (tanh 0): open them
        cross = params["cross_blocks"]
        params["cross_blocks"] = {**cross,
                                  "gate": jnp.full_like(cross["gate"], 0.5)}
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                      (BATCH, PROMPT + DECODE), np.int32))
    media = (jnp.asarray(rng.normal(
        size=(BATCH, cfg.n_media_tokens, cfg.media_embed_dim)),
        jnp.float32) if cfg.n_media_tokens else None)
    prompt = tokens[:, :PROMPT]
    batch = {"tokens": prompt} if media is None else {"tokens": prompt,
                                                      "media": media}
    out = {"forward": jax.jit(m.forward)(params, batch)}
    max_len = PROMPT + DECODE + (cfg.n_media_tokens
                                 if cfg.family == "audio" else 0)
    cache = m.init_cache(BATCH, max_len)
    out["prefill"], cache = jax.jit(m.prefill)(params, cache, prompt, media)
    step = jax.jit(m.decode_step)
    decoded = []
    for t in range(PROMPT, PROMPT + DECODE):
        lg, cache = step(params, cache, tokens[:, t:t + 1], None)
        decoded.append(lg)
    out["decode"] = jnp.concatenate(decoded, axis=1)
    return {k: np.asarray(v, np.float32) for k, v in out.items()}


def capture() -> dict[str, np.ndarray]:
    return {f"{arch}/{k}": v for arch in registry.ARCHS
            for k, v in logits(arch).items()}


if __name__ == "__main__":
    np.savez_compressed(GOLDEN_PATH, **capture())
    print(f"wrote {GOLDEN_PATH}")
