"""granite-4.0-h (Mamba-2 layers with NoPE attention layers, an MLP after
every mixer) against the plain float32 reference, at a small size on seeded
random weights: prefill and decode through the cache, the chunked SSD
against the token-by-token recurrence, padding, and the per-kind cache."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import model as model_lib
from repro.models import reference, ssm

ARCH = "granite-4.0-h-micro"


def _small(dtype="float32"):
    return dataclasses.replace(registry.get(ARCH).reduced(), dtype=dtype)


VECTORS = ("ln", "ln1", "ln2", "final_norm", "conv_b", "A_log", "dt_bias",
           "D", "norm_w")


def _random_params(model, seed=0):
    """``Model.init``'s weights with every vector leaf (norm gains, conv
    bias, A_log, dt_bias, D) drawn too, so that none of them sits at a
    value that hides a mistake (a gain of 0, A = -1 for every head)."""
    key = jax.random.key(seed + 1)

    def draw(path, a):
        name = path[-1].key
        if name not in VECTORS:
            return a
        k = jax.random.fold_in(key, VECTORS.index(name) * 1000 + a.size)
        return a + (0.3 * jax.random.normal(k, a.shape)).astype(a.dtype)

    return jax.tree_util.tree_map_with_path(
        draw, model.init(jax.random.key(seed)))


def test_config_is_as_published():
    cfg = registry.get(ARCH)
    attn = [i for i, t in enumerate(cfg.layer_types) if t == "attention"]
    assert attn == [5, 15, 25, 35] and cfg.n_layers == 40
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.d_ff, cfg.vocab_size) == (2048, 32, 8, 64, 8192, 100_352)
    assert (cfg.d_inner, cfg.d_inner // cfg.ssm_head_dim, cfg.ssm_state,
            cfg.ssm_groups, cfg.ssm_conv, cfg.ssm_chunk) == (
                4096, 64, 128, 1, 4, 256)
    assert ssm.conv_width(cfg) == 4096 + 2 * 128
    shapes = jax.eval_shape(model_lib.build(cfg).init, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    assert 3.18e9 < n < 3.20e9, n
    assert shapes["blocks"]["mixer"]["in_proj"].shape == (36, 2048, 8512)


def test_prefill_then_decode_matches_reference():
    """Prefill of an 11-token prompt (chunks of 8: one whole, one partial)
    and 6 decode steps through the cache, against the reference's full
    forward over the same 17 tokens.  In float32 the two differ only in
    the order of their sums (chunked SSD against the recurrence, blocked
    against whole softmax, f32 matmuls at CPU precision), some 1e-6 of
    the logits' scale; 1e-4 of it leaves room for that and fails any
    mistake in the mathematics."""
    cfg = _small()
    model = model_lib.build(cfg)
    params = _random_params(model)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(2, cfg.vocab_size, (2, 17)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference.forward(cfg, params, toks))
    tol = 1e-4 * np.abs(ref).max()
    cache = model.init_cache(2, 32)
    logits, cache = jax.jit(model.prefill)(params, cache, toks[:, :11])
    np.testing.assert_allclose(logits[:, 0], ref[:, 10], rtol=0, atol=tol)
    step = jax.jit(model.decode_step, donate_argnums=(1,))
    for t in range(11, 17):
        logits, cache = step(params, cache, toks[:, t:t + 1])
        np.testing.assert_allclose(logits[:, 0], ref[:, t], rtol=0,
                                   atol=tol)
    full = jax.jit(model.forward)(params, {"tokens": toks})
    np.testing.assert_allclose(full, ref, rtol=0, atol=tol)


def test_bfloat16_program_follows_reference():
    """In bfloat16, the serving dtype, the logits stay within 10% of the
    reference logits' spread.  bfloat16 keeps 8 significant bits, so each
    weight, activation and logit is rounded by up to 0.4%, and the
    roundings of 4 layers add up to about 5% here (seen 4.6%); a dropped
    or misplaced term moves the logits by a sizable share of their
    spread."""
    cfg = _small("bfloat16")
    model = model_lib.build(cfg)
    params = _random_params(model, seed=3)
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(2, cfg.vocab_size, (1, 14)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(reference.forward(cfg, params, toks))
    cache = model.init_cache(1, 16)
    logits, cache = jax.jit(model.prefill)(params, cache, toks[:, :9])
    got = [np.asarray(logits[0, 0], np.float32)]
    for t in range(9, 13):
        logits, cache = jax.jit(model.decode_step)(params, cache,
                                                   toks[:, t:t + 1])
        got.append(np.asarray(logits[0, 0], np.float32))
    want = ref[0, 8:13]
    assert np.abs(np.stack(got) - want).max() <= 0.1 * want.std()


@pytest.mark.parametrize("T,groups", [(8, 1), (37, 1), (37, 2), (1, 2)])
def test_chunked_ssd_matches_recurrence(T, groups):
    """Chunks of 8 over 8 and 37 steps (4 whole chunks and a partial
    one), from a nonzero state, with one or two groups of B and C; T = 1
    is the decode step."""
    Bt, H, P, N = 2, 4, 3, 5
    ks = jax.random.split(jax.random.key(T + groups), 6)
    x = jax.random.normal(ks[0], (Bt, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, T, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (Bt, T, groups, N))
    C = jax.random.normal(ks[4], (Bt, T, groups, N))
    h0 = jax.random.normal(ks[5], (Bt, H, P, N))
    with jax.default_matmul_precision("highest"):
        want_y, want_h = reference.ssm_recurrence(x, dt, A, B, C, h0)
        if T == 1:
            y, h = ssm.ssd_step(x, dt, A, B, C, h0)
        else:
            y, h = ssm.ssd_chunked(x, dt, A, B, C, h0, chunk=8)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h, want_h, rtol=1e-5, atol=1e-5)


def test_padding_leaves_the_state_unchanged():
    """Left padding ahead of a prompt on an empty cache, masked, leaves
    the conv window and the SSM state as they were: after the padded
    prompt they equal those after the prompt alone, and so do the
    prompt's outputs."""
    cfg = _small()
    p = _random_params(model_lib.build(cfg))["blocks"]["mixer"]
    p = jax.tree.map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.key(5), (2, 10, cfg.d_model))
    pad = jax.random.normal(jax.random.key(6), (2, 3, cfg.d_model))
    mask = jnp.arange(13)[None].repeat(2, 0) >= 3
    y, (conv, h) = ssm.mamba2_block(p, x, cfg)
    yp, (convp, hp) = ssm.mamba2_block(
        p, jnp.concatenate([pad, x], 1), cfg, mask=mask)
    np.testing.assert_allclose(yp[:, 3:], y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(convp, conv, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(hp, h, rtol=1e-5, atol=1e-5)
    # unmasked, the same padding does change the state
    _, (_, hu) = ssm.mamba2_block(p, jnp.concatenate([pad, x], 1), cfg)
    assert np.abs(hu - h).max() > 1e-3


def test_init_cache_holds_each_kind_once():
    """KV for the 4 attention layers only; conv window and SSM state for
    the 36 mamba layers only (zamba2: its 54 mamba layers and 9 shared
    attention calls)."""
    for arch, n_ssm, n_attn, conv in (("granite-4.0-h-micro", 36, 4, 4352),
                                      ("zamba2-2.7b", 54, 9, 5248)):
        cfg = registry.get(arch)
        c = jax.eval_shape(lambda: model_lib.build(cfg).init_cache(32, 2304))
        assert set(c) == {"pos", "conv", "h", "k", "v"}
        assert c["conv"].shape == (n_ssm, 32, 3, conv)
        assert c["h"].shape == (n_ssm, 32, cfg.d_inner // 64, 64,
                                cfg.ssm_state)
        assert c["h"].dtype == jnp.float32
        assert c["k"].shape == c["v"].shape == (
            n_attn, 32, 2304, cfg.n_kv_heads, cfg.head_dim)
    c = jax.eval_shape(lambda: model_lib.build(registry.get(
        "granite-4.0-h-micro")).init_cache(32, 2304))
    # per sequence, 36 x 2 MiB of SSM state and 36 x 26 KiB of conv
    # window; 8 KiB of KV per token
    assert c["h"].size * 4 / 32 == 36 * 2**21
    assert c["conv"].size * 2 / 32 == 36 * 3 * 4352 * 2
    assert c["k"].size * 2 * 2 / (32 * 2304) == 8192


def test_with_depth_keeps_whole_periods():
    cfg = registry.get(ARCH)
    cut = cfg.with_depth(20)
    assert cut.layer_types == cfg.layer_types[:20]
    with pytest.raises(ValueError):
        cfg.with_depth(12)
