"""Per-architecture smoke tests: reduced config, one forward + train-grad +
decode step on CPU, asserting shapes and finiteness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import model as model_lib

ARCHS = list(registry.ARCHS)


def _batch(cfg, B=2, T=16):
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, T), dtype=np.int32))}
    if cfg.n_media_tokens:
        batch["media"] = jnp.asarray(
            rng.normal(size=(B, cfg.n_media_tokens, cfg.media_embed_dim))
            .astype(np.float32))
    return batch


@pytest.fixture(scope="module")
def built():
    out = {}
    for arch in ARCHS:
        cfg = registry.get(arch).reduced()
        m = model_lib.build(cfg)
        params = m.init(jax.random.key(0))
        out[arch] = (cfg, m, params)
    return out


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(built, arch):
    cfg, m, params = built[arch]
    batch = _batch(cfg)
    logits = jax.jit(m.forward)(params, batch)
    B, T = batch["tokens"].shape
    assert logits.shape == (B, T, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all()), \
        f"{arch}: non-finite logits"


@pytest.mark.parametrize("arch", ARCHS)
def test_train_loss_and_grads_finite(built, arch):
    cfg, m, params = built[arch]
    batch = _batch(cfg)
    loss, grads = jax.jit(jax.value_and_grad(m.train_loss))(params, batch)
    assert bool(jnp.isfinite(loss)), f"{arch}: loss={loss}"
    leaves = jax.tree.leaves(grads)
    assert leaves, "no grads"
    for g in leaves:
        assert bool(jnp.isfinite(g.astype(jnp.float32)).all()), \
            f"{arch}: non-finite grad"


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step(built, arch):
    cfg, m, params = built[arch]
    B, S = 2, 32
    cache = m.init_cache(B, S)
    # simulate a cache mid-sequence
    cache["pos"] = jnp.asarray(7, jnp.int32)
    tok = jnp.ones((B, 1), jnp.int32)
    media = (jnp.zeros((B, cfg.n_media_tokens, cfg.media_embed_dim),
                       jnp.float32) if cfg.n_media_tokens else None)
    logits, cache2 = jax.jit(m.decode_step)(params, cache, tok, media)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert int(cache2["pos"]) == 8


@pytest.mark.parametrize("arch,key,seed", [
    ("granite-3-2b", 1, 3),        # dense attention
    ("falcon-mamba-7b", 2, 4),     # recurrent decode == parallel scan
])
def test_decode_matches_forward(arch, key, seed):
    """Greedy decode logits must match teacher-forced forward logits."""
    cfg = registry.get(arch).reduced()
    m = model_lib.build(cfg)
    params = m.init(jax.random.key(key))
    rng = np.random.default_rng(seed)
    T = 8
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, T), np.int32))
    full = m.forward(params, {"tokens": toks})
    cache = m.init_cache(1, T)
    step = jax.jit(m.decode_step)
    for t in range(T):
        logits, cache = step(params, cache, toks[:, t:t + 1], None)
        np.testing.assert_allclose(
            np.asarray(logits[0, 0]), np.asarray(full[0, t]),
            rtol=2e-2, atol=2e-2)


def test_local_global_flags_gemma():
    cfg = registry.get("gemma2-9b")
    m = model_lib.build(cfg)
    flags = np.asarray([step.is_global for step in m._schedule()])
    assert flags.shape == (42,)
    assert flags[1::2].all() and not flags[0::2].any()


def test_full_configs_match_spec():
    """Assigned-architecture hyperparameters are exactly as listed."""
    spec = {
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 1408, 151_936),
        "llama4-maverick-400b-a17b": (48, 5120, 40, 8, 8192, 202_048),
        "gemma3-1b": (26, 1152, 4, 1, 6912, 262_144),
        "granite-3-2b": (40, 2048, 32, 8, 8192, 49_155),
        "gemma2-9b": (42, 3584, 16, 8, 14_336, 256_000),
        "glm4-9b": (40, 4096, 32, 2, 13_696, 151_552),
        "zamba2-2.7b": (54, 2560, 32, 32, 10_240, 32_000),
        "falcon-mamba-7b": (64, 4096, 0, 0, 0, 65_024),
        "llama-3.2-vision-11b": (40, 4096, 32, 8, 14_336, 128_256),
    }
    for arch, (L, d, h, kv, ff, v) in spec.items():
        cfg = registry.get(arch)
        ff_actual = cfg.moe_d_ff if cfg.family == "moe" else cfg.d_ff
        got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
               ff_actual, cfg.vocab_size)
        assert got == (L, d, h, kv, ff, v), f"{arch}: {got}"
    assert registry.get("zamba2-2.7b").ssm_state == 64
    assert registry.get("falcon-mamba-7b").ssm_state == 16
