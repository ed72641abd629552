"""Plain float32 reference of granite-4.0-h's forward pass.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``precision="highest"``: no cache, no chunking, no kernels, and nothing
imported from the model code it checks.  It reads the weights from the
program's parameter tree (``Model.init`` layout: ``blocks`` holds the
mamba layers in order, ``attn_blocks`` the attention layers) and computes

    x = embed[tokens] * embedding_multiplier
    per layer i:  x += r * Mixer_i(RMSNorm(x));  x += r * MLP(RMSNorm(x))
    logits = RMSNorm(x) @ embed.T / logits_scaling

with r the residual multiplier and Mixer_i as ``layer_types[i]`` says:

* attention: causal grouped-query attention with no position embedding
  (NoPE), scores scaled by ``attention_multiplier``;
* mamba: the published Mamba-2 mixer.  ``in_proj`` gives z, xBC and dt; a
  depthwise causal conv (with bias) and SiLU over xBC, split into x, B and
  C; dt = softplus(dt + dt_bias), A = -exp(A_log); per head, token by
  token, S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T and y_t = S_t C_t +
  D x_t; then RMSNorm(y * silu(z)) over each group's channels and
  ``out_proj``;
* MLP: SiLU-gated, after every mixer.

Departures from the published model, in the reference and the program
alike:

* RMSNorm gains are stored as g with the norm scaling by (1 + g), where
  the published model stores the weight w = 1 + g: the same function.
* The weights are whatever the caller passes (tests and the benchmark
  draw them at random); the published checkpoint is not loaded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + gain)


def ssm_recurrence(x, dt, A, B, C, h0):
    """The Mamba-2 state recurrence one token at a time.

    x: (Bt, T, H, P); dt: (Bt, T, H); A: (H,); B, C: (Bt, T, G, N), head h
    reading group h // (H / G); h0: (Bt, H, P, N).  Returns (y (Bt, T, H,
    P) without the D skip, final state)."""
    H, G = x.shape[2], B.shape[2]
    Bh = jnp.repeat(B, H // G, axis=2)                   # (Bt, T, H, N)
    Ch = jnp.repeat(C, H // G, axis=2)

    def step(S, inp):
        xt, dtt, bt, ct = inp                            # (Bt, H, ...)
        S = (S * jnp.exp(dtt * A)[..., None, None]
             + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, ct, precision=HIGHEST)

    seq = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, Bh, Ch))
    S, y = jax.lax.scan(step, h0, seq)
    return jnp.moveaxis(y, 0, 1), S


def mamba2(p, x, cfg):
    """The published Mamba-2 mixer over a whole sequence; x: (Bt, T, d)."""
    Bt, T, _ = x.shape
    di, n, P, G = (cfg.d_inner, cfg.ssm_state, cfg.ssm_head_dim,
                   cfg.ssm_groups)
    H, K = di // P, cfg.ssm_conv
    zxbcdt = _mm("btd,de->bte", x, p["in_proj"])
    z, xbc, dt = zxbcdt[..., :di], zxbcdt[..., di:-H], zxbcdt[..., -H:]
    # depthwise causal conv: out_t = b + sum_k w_k xbc_{t-K+1+k}
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = p["conv_b"] + sum(padded[:, k:k + T] * p["conv_w"][k]
                             for k in range(K))
    xbc = jax.nn.silu(conv)
    xs, Bm, Cm = xbc[..., :di], xbc[..., di:di + G * n], xbc[..., di + G * n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(Bt, T, H, P)
    y, _ = ssm_recurrence(xh, dt, A, Bm.reshape(Bt, T, G, n),
                          Cm.reshape(Bt, T, G, n),
                          jnp.zeros((Bt, H, P, n), jnp.float32))
    y = y + p["D"][:, None] * xh
    g = (y.reshape(Bt, T, G, di // G)
         * jax.nn.silu(z).reshape(Bt, T, G, di // G))
    y = rms_norm(g, p["norm_w"].reshape(G, -1), cfg.norm_eps)
    return _mm("bti,id->btd", y.reshape(Bt, T, di), p["out_proj"])


def attention(p, x, cfg):
    """Causal GQA, NoPE, scores scaled by ``attention_multiplier``."""
    T = x.shape[1]
    q = _mm("btd,dhk->bthk", x, p["wq"])
    k = _mm("btd,dhk->bthk", x, p["wk"])
    v = _mm("btd,dhk->bthk", x, p["wv"])
    rep = cfg.n_heads // cfg.n_kv_heads
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = _mm("bthk,bshk->bhts", q, k) * cfg.attention_multiplier
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    out = _mm("bhts,bshk->bthk", jax.nn.softmax(s, axis=-1), v)
    return _mm("bthk,hkd->btd", out, p["wo"])


def mlp(p, x):
    g = jax.nn.silu(_mm("btd,df->btf", x, p["wi_gate"]))
    return _mm("btf,fd->btd", g * _mm("btd,df->btf", x, p["wi_up"]),
               p["wo"])


def forward(cfg, params, tokens):
    """Logits (B, T, vocab), float32, of a granite-4.0-h configuration
    (``cfg.layer_types`` set) for tokens (B, T)."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    r = cfg.residual_multiplier
    x = w["embed"][tokens] * cfg.embedding_multiplier
    seen = {"mamba": 0, "attention": 0}
    for kind in cfg.layer_types:
        stack = w["blocks"] if kind == "mamba" else w["attn_blocks"]
        blk = jax.tree.map(lambda a: a[seen[kind]], stack)
        seen[kind] += 1
        if kind == "mamba":
            h = rms_norm(x, blk["ln"], cfg.norm_eps)
            x = x + r * mamba2(blk["mixer"], h, cfg)
        else:
            h = rms_norm(x, blk["ln1"], cfg.norm_eps)
            x = x + r * attention(blk["attn"], h, cfg)
        x = x + r * mlp(blk["mlp"], rms_norm(x, blk["ln2"], cfg.norm_eps))
    x = rms_norm(x, w["final_norm"], cfg.norm_eps)
    return _mm("btd,vd->btv", x, w["embed"]) / cfg.logits_scaling
