"""Plain float32 reference of the layer-pattern hybrid the cell runs
(granite-4.0-h), for the serving check.

Straightforward ``jax.numpy``: no cache, no chunking, no kernels, every
matrix product at ``precision="highest"``.  It imports nothing of the
program; its weights are drawn again from the seed by
``hybrid_weights.py``.  It computes

    x = embed[tokens] * embedding_multiplier
    per layer:  x += r * Mixer(RMSNorm(x));  x += r * MLP(RMSNorm(x))
    logits = RMSNorm(x) @ embed.T / logits_scaling

with r the residual multiplier, RMSNorm(x) = x / sqrt(mean(x^2) + eps) *
(1 + gain), and the mixer ``layer_types`` names:

* attention: causal grouped-query attention, no position embedding,
  scores scaled by ``attention_multiplier``;
* mamba: the published Mamba-2 mixer.  ``in_proj`` to z, xBC and dt; a
  depthwise causal conv with bias and SiLU over xBC, split into x, B, C;
  dt = softplus(dt + dt_bias), A = -exp(A_log); per head, one token at a
  time, S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t + D x_t;
  RMSNorm(y * silu(z)) over each group's channels; ``out_proj``;
* the SiLU-gated MLP.

Departure from the published model: norm gains are stored as g with the
norm scaling by (1 + g) (the published weight is 1 + g), as in the
program; the configuration file lists no other.

Checked layer by layer over the sampled sequences, one layer's weights
on the device at a time.  ``cast`` is where the low-precision control
departs, as in ``reference.py``: every matrix-product input rounded to
float8 (e4m3) with per-tensor scaling; the state recurrence, not a matrix
product, stays in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import hybrid_weights, weights
from benchmarks.chip.hybrid_shapes import HybridSizes
from benchmarks.chip.reference import CASTS, PAD_TO, Q_BLOCK, _mm, rms_norm


def attention(h, a, s: HybridSizes, cast):
    """Causal GQA over h (1, T, d), in blocks of query rows."""
    T = h.shape[1]
    K, G, Dh = s.kv_heads, s.heads // s.kv_heads, s.head_dim
    q = _mm("btd,dhk->bthk", h, a["wq"], cast)
    k = _mm("btd,dhk->bthk", h, a["wk"], cast)
    v = _mm("btd,dhk->bthk", h, a["wv"], cast)
    qb = min(Q_BLOCK, T)
    q = q.reshape(1, T // qb, qb, K, G, Dh).transpose(1, 0, 2, 3, 4, 5)

    def block(args):
        i, qi = args
        sc = _mm("bqkgd,bskd->bkgqs", qi, k, cast) * s.attention_multiplier
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(qpos[:, None] >= jnp.arange(T)[None, :], sc, -jnp.inf)
        return _mm("bkgqs,bskd->bqkgd", jax.nn.softmax(sc, axis=-1), v, cast)

    out = jax.lax.map(block, (jnp.arange(T // qb), q))
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(1, T, s.heads, Dh)
    return _mm("bthk,hkd->btd", out, a["wo"], cast)


def mamba2(h, p, s: HybridSizes, cast):
    """The Mamba-2 mixer over h (1, T, d), the state one token at a time."""
    T = h.shape[1]
    di, H, P, N, G = (s.d_inner, s.mamba_heads, s.mamba_head_dim, s.state,
                      s.groups)
    zxbcdt = _mm("btd,de->bte", h, p["in_proj"], cast)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:di + s.conv_dim],
                  zxbcdt[..., di + s.conv_dim:])
    padded = jnp.pad(xbc, ((0, 0), (s.conv - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(padded[:, j:j + T] * p["conv_w"][j]
                                        for j in range(s.conv)))
    x = xbc[0, :, :di].reshape(T, H, P)
    B = jnp.repeat(xbc[0, :, di:di + G * N].reshape(T, G, N), H // G, 1)
    C = jnp.repeat(xbc[0, :, di + G * N:].reshape(T, G, N), H // G, 1)
    dt = jax.nn.softplus(dt[0] + p["dt_bias"])                 # (T, H)
    A = -jnp.exp(p["A_log"])

    def step(S, inp):
        xt, dtt, bt, ct = inp
        S = (S * jnp.exp(dtt * A)[:, None, None]
             + (dtt[:, None] * xt)[..., None] * bt[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, ct,
                             precision=jax.lax.Precision.HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, B, C))
    y = y + p["D"][:, None] * x
    g = y.reshape(T, G, di // G) * jax.nn.silu(z[0]).reshape(T, G, -1)
    y = rms_norm(g, p["norm_w"].reshape(G, -1), s.norm_eps)
    return _mm("bti,id->btd", y.reshape(1, T, di), p["out_proj"], cast)


def mlp(h, m, cast):
    g = jax.nn.silu(_mm("btd,df->btf", h, m["wi_gate"], cast))
    return _mm("btf,fd->btd", g * _mm("btd,df->btf", h, m["wi_up"], cast),
               m["wo"], cast)


def layer(w, x, kind: str, s: HybridSizes, cast):
    r = s.residual_multiplier
    if kind == "mamba":
        x = x + r * mamba2(rms_norm(x, w["ln"], s.norm_eps), w["mixer"], s,
                           cast)
    else:
        x = x + r * attention(rms_norm(x, w["ln1"], s.norm_eps), w["attn"],
                              s, cast)
    return x + r * mlp(rms_norm(x, w["ln2"], s.norm_eps), w["mlp"], cast)


def embed(head, tokens, s: HybridSizes):
    return head["embed"].astype(jnp.float32)[tokens] * s.embedding_multiplier


def logits(head, x, s: HybridSizes, cast):
    x = rms_norm(x, head["final_norm"], s.norm_eps)
    w = head["embed"].T if s.tied else head["unembed"]
    return _mm("btd,dv->btv", x, w.astype(jnp.float32), cast) \
        / s.logits_scaling


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


class ServeReference:
    """Logits of the reference (or its control) at the positions whose next
    token was served, for a few sequences, layer by layer."""

    def __init__(self, seed: int, s: HybridSizes, embed_std: float):
        self.key = weights.root_key(seed)
        self.s = s
        self._head = jax.jit(lambda k: _f32(
            weights.head_weights(k, s, embed_std)))
        self._layer_w = {
            "mamba": jax.jit(lambda k, i: _f32(
                hybrid_weights.mamba_layer_weights(k, s, i))),
            "attention": jax.jit(lambda k, i: _f32(
                weights.layer_weights(k, s, i)))}
        self._layer = {(kind, name): jax.jit(functools.partial(
            layer, kind=kind, s=s, cast=cast))
            for kind in ("mamba", "attention")
            for name, cast in CASTS.items()}
        self._embed = jax.jit(functools.partial(embed, s=s))
        self._logits = {name: jax.jit(functools.partial(
            logits, s=s, cast=cast)) for name, cast in CASTS.items()}

    def logits(self, seqs: list[np.ndarray], firsts: list[int],
               cast: str = "reference") -> list[jax.Array]:
        """For each sequence, float32 logits at positions first-1 .. len-2,
        i.e. the logits that chose tokens first .. len-1."""
        with jax.default_matmul_precision("highest"):
            head = self._head(self.key)
            xs = []
            for seq in seqs:
                n = -(-(len(seq) - 1) // PAD_TO) * PAD_TO
                tok = np.zeros((1, n), np.int32)
                tok[0, :len(seq) - 1] = seq[:-1]
                xs.append(self._embed(head, jnp.asarray(tok)))
            for i, kind in enumerate(self.s.layer_types):
                w = self._layer_w[kind](self.key, i)
                xs = [self._layer[kind, cast](w, x) for x in xs]
                del w
            return [self._logits[cast](head, x[:, f - 1:len(seq) - 1])[0]
                    for x, f, seq in zip(xs, firsts, seqs)]
