"""Plain float32 reference of the dense decoder that the cells run.

Straightforward ``jax.numpy``: no cache, no kernels, no batching of
requests, every matrix product at ``precision="highest"``.  It imports
nothing of the program and takes nothing the program made: its weights are
drawn again from the seed by ``weights.py``, and it computes

    x = embed[tokens] * (sqrt(d) if the embedding is tied else 1)
    per layer:  x += Attn(RMSNorm(x));  x += MLP(RMSNorm(x))
    logits = RMSNorm(x) @ (embed.T if tied else unembed)

with RMSNorm(x) = x / sqrt(mean(x^2) + eps) * (1 + gain), rotary position
embedding on the two halves of each head, causal grouped-query attention
scaled by 1/sqrt(head_dim), and a SiLU-gated MLP.  Those are the equations
of the program's dense family; where they depart from a published model
(granite's multipliers, glm-4's partial rotary and q/k/v bias) the
configuration file lists the departure under ``departures``.

Serving is checked layer by layer over the sampled sequences, so one
layer's weights are on the device at a time.  Training keeps the whole
(cut) model in float32 and runs one sequence at a time.

``cast`` is where the control departs: the identity for the reference,
and for the low-precision control float8 as fp8 matrix products are run,
with per-tensor scaling: every matrix-product input rounded to e4m3 and,
in the backward pass, every gradient flowing into one rounded to e5m2.
The control keeps its weights, moments and updates in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights
from benchmarks.chip.shapes import Sizes

Q_BLOCK = 128          # query rows per attention block
PAD_TO = Q_BLOCK       # sequences are right-padded to a multiple of this


def exact(x):
    return x


def _scaled_round(x, dtype):
    """Round ``x`` to ``dtype`` with the tensor's largest magnitude scaled to
    the format's largest finite value, and scale back."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, jnp.finfo(dtype).max.astype(jnp.float32)
                      / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8(x):
    return _scaled_round(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_scaled_round(g, jnp.float8_e5m2),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


CASTS = {"reference": exact, "fp8": fp8}


def _mm(spec, a, b, cast):
    return jnp.einsum(spec, cast(a), cast(b),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * (1.0 + gain.astype(jnp.float32)))


def rope(x, theta):
    """x: (B, T, heads, Dh), positions 0..T-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, cast):
    """Causal grouped-query attention, in blocks of query rows.
    q: (B, T, H, Dh); k, v: (B, T, K, Dh)."""
    B, T, H, Dh = q.shape
    K = k.shape[2]
    qb = min(Q_BLOCK, T)
    q = q.reshape(B, T // qb, qb, K, H // K, Dh).transpose(1, 0, 2, 3, 4, 5)

    def block(args):
        i, qi = args
        s = _mm("bqkgd,bskd->bkgqs", qi, k, cast) * Dh ** -0.5
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(qpos[:, None] >= jnp.arange(T)[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("bkgqs,bskd->bqkgd", p, v, cast)

    out = jax.lax.map(block, (jnp.arange(T // qb), q))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, T, H, Dh)


def layer(w, x, s: Sizes, cast):
    h = rms_norm(x, w["ln1"], s.norm_eps)
    a = w["attn"]
    q = rope(_mm("btd,dhk->bthk", h, a["wq"], cast), s.rope_theta)
    k = rope(_mm("btd,dhk->bthk", h, a["wk"], cast), s.rope_theta)
    v = _mm("btd,dhk->bthk", h, a["wv"], cast)
    x = x + _mm("bthk,hkd->btd", attention(q, k, v, cast), a["wo"], cast)
    h = rms_norm(x, w["ln2"], s.norm_eps)
    m = w["mlp"]
    g = jax.nn.silu(_mm("btd,df->btf", h, m["wi_gate"], cast))
    u = _mm("btd,df->btf", h, m["wi_up"], cast)
    return x + _mm("btf,fd->btd", g * u, m["wo"], cast)


def embed(head, tokens, s: Sizes):
    x = head["embed"].astype(jnp.float32)[tokens]
    return x * s.d ** 0.5 if s.tied else x


def logits(head, x, s: Sizes, cast):
    x = rms_norm(x, head["final_norm"], s.norm_eps)
    w = head["embed"].T if s.tied else head["unembed"]
    return _mm("btd,dv->btv", x, w.astype(jnp.float32), cast)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


# --- serving ----------------------------------------------------------------

class ServeReference:
    """Logits of the reference (or its control) at the positions whose next
    token was served, for a few sequences, layer by layer."""

    def __init__(self, seed: int, s: Sizes, embed_std: float):
        self.key = weights.root_key(seed)
        self.s = s
        self.embed_std = embed_std
        self._head = jax.jit(lambda k: _f32(
            weights.head_weights(k, s, embed_std)))
        self._layer_w = jax.jit(lambda k, i: _f32(
            weights.layer_weights(k, s, i)))
        self._layer = {name: jax.jit(functools.partial(
            layer, s=s, cast=cast)) for name, cast in CASTS.items()}
        self._embed = jax.jit(functools.partial(embed, s=s))
        self._logits = {name: jax.jit(functools.partial(
            logits, s=s, cast=cast)) for name, cast in CASTS.items()}

    def logits(self, seqs: list[np.ndarray], firsts: list[int],
               cast: str = "reference") -> list[jax.Array]:
        """For each sequence, float32 logits at positions first-1 .. len-2,
        i.e. the logits that chose tokens first .. len-1 (the last token is
        not read)."""
        with jax.default_matmul_precision("highest"):
            head = self._head(self.key)
            xs = []
            for seq in seqs:
                n = -(-(len(seq) - 1) // PAD_TO) * PAD_TO
                tok = np.zeros((1, n), np.int32)
                tok[0, :len(seq) - 1] = seq[:-1]
                xs.append(self._embed(head, jnp.asarray(tok)))
            for i in range(self.s.layers):
                w = self._layer_w(self.key, i)
                xs = [self._layer[cast](w, x) for x in xs]
                del w
            return [self._logits[cast](head, x[:, f - 1:len(seq) - 1])[0]
                    for x, f, seq in zip(xs, firsts, seqs)]


# --- training ---------------------------------------------------------------

def sequence_loss(params, tokens, s: Sizes, cast):
    """Mean next-token cross-entropy of one (1, T) sequence."""
    x = embed(params, tokens, s)

    # recomputed in the backward pass, so that one layer's activations are
    # held at a time; the arithmetic is unchanged
    @jax.checkpoint
    def body(x, w):
        return layer(w, x, s, cast), None

    x, _ = jax.lax.scan(body, x, params["blocks"])
    lg = logits(params, x, s, cast)[:, :-1]
    gold = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)


def adamw_schedule(opt: dict, step: int) -> float:
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    t = min(max((step - opt["warmup_steps"])
                / max(opt["total_steps"] - opt["warmup_steps"], 1), 0), 1)
    cos = 0.5 * (1 + np.cos(np.pi * t))
    return opt["lr"] * warm * (opt["min_lr_ratio"]
                               + (1 - opt["min_lr_ratio"]) * cos)


def adamw_update(opt: dict, step: int, params, grads, m, v):
    """AdamW as the traffic file states it: global-norm clipping, bias
    corrected moments, decoupled weight decay, warm-up then cosine."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))
    lr = adamw_schedule(opt, step)
    b1, b2 = opt["b1"], opt["b2"]
    bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
    grads = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, m_, v_: p - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2)
                                                  + opt["eps"])
                                    + opt["weight_decay"] * p),
        params, m, v)
    return params, m, v, grads


def leaf_norms(tree) -> dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32))))) for p, x in flat}


def train_reference(seed: int, s: Sizes, embed_std: float,
                    batches: list[np.ndarray], opt: dict,
                    cast: str = "reference", rows: int | None = None
                    ) -> dict:
    """Three (or ``len(batches)``) AdamW steps from the seed's weights.

    Returns each step's loss, the leaf norms of the first step's clipped
    gradient, and the leaf norms of the weights' change over all steps.
    ``rows`` keeps only the first rows of each batch (the half-batch
    fault)."""
    key = weights.root_key(seed)
    params = jax.jit(lambda k: _f32(weights.serving_weights(
        k, s, embed_std)))(key)
    start = params
    grad_fn = jax.jit(jax.value_and_grad(functools.partial(
        sequence_loss, s=s, cast=CASTS[cast])))
    update = jax.jit(functools.partial(adamw_update, opt),
                     static_argnums=0)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for step, batch in enumerate(batches, start=1):
            batch = batch[:rows] if rows else batch
            loss, grads = 0.0, jax.tree.map(jnp.zeros_like, params)
            for row in batch:
                l_, g_ = grad_fn(params, jnp.asarray(row[None]))
                loss += float(l_)
                grads = jax.tree.map(jnp.add, grads, g_)
            grads = jax.tree.map(lambda g: g / len(batch), grads)
            params, m, v, clipped = update(step, params, grads, m, v)
            losses.append(loss / len(batch))
            if first_grad is None:
                first_grad = leaf_norms(clipped)
            del grads, clipped
    change = leaf_norms(jax.tree.map(jnp.subtract, params, start))
    return {"losses": losses, "grad_norms": first_grad,
            "change_norms": change}
