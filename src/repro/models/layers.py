"""Shared transformer layer primitives (pure functional JAX).

Everything here is config-driven and shape-polymorphic so one implementation
serves all ten assigned architectures: RMSNorm, RoPE, GQA attention with an
online-softmax KV-block scan (causal, sliding-window, logit softcap — no
O(T^2) mask materialization) or, for prefill and training on a TPU, a fused
Pallas flash kernel, one token's attention over its cache (on a TPU a Pallas
kernel that reads only the blocks up to its position), and (Sw/Ge)GLU MLPs.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

Params = dict[str, Any]

NEG_INF = -1e30

# Layer-kind scopes (``jax.named_scope``), one name per kind of work.  They
# change no instruction of a compiled program, only its ``op_name``
# metadata, where a profiler trace's reduction finds each operation's kind
# (the innermost of these names in its path; ``kv_cache`` nests inside
# ``attention``).
EMBED = "embed"
ATTENTION = "attention"
KV_CACHE = "kv_cache"
MLP = "mlp"
MOE = "moe"
SSM = "ssm"
UNEMBED = "unembed"
LOSS = "loss"
OPTIMIZER = "optimizer"
SCOPES = (EMBED, ATTENTION, KV_CACHE, MLP, MOE, SSM, UNEMBED, LOSS,
          OPTIMIZER)


# --- initialization helpers ------------------------------------------------------

def dense_init(key, in_dim: int, out_shape: tuple[int, ...], dtype) -> jax.Array:
    scale = 1.0 / (in_dim ** 0.5)
    return (jax.random.normal(key, (in_dim, *out_shape), jnp.float32)
            * scale).astype(dtype)


# --- norms -----------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + weight.astype(jnp.float32))
    return out.astype(dt)


# --- rotary embeddings ----------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Apply rotary embeddings.  x: (..., T, H, Dh); positions: (..., T)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs      # (..., T, half)
    cos = jnp.cos(angles)[..., None, :]                            # (..., T, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _softcap(s: jax.Array, cap: float) -> jax.Array:
    return cap * jnp.tanh(s / cap) if cap > 0.0 else s


# --- attention -------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int = 0               # >0: sliding window size
    softcap: float = 0.0
    kv_block: int = 512
    scale: float = 0.0            # score scale; 0 -> 1/sqrt(head_dim)


def attention(q: jax.Array, k: jax.Array, v: jax.Array,
              spec: AttnSpec, *,
              q_offset: jax.Array | int = 0,
              is_global: jax.Array | bool = True,
              kv_len: jax.Array | None = None) -> jax.Array:
    """Causal attention of q over k, v.

    q: (B, Tq, H, Dh); k, v: (B, Tk, K, Dh).  Causal with optional sliding
    window (disabled when ``is_global``) and logit soft-capping.  ``q_offset``
    is the absolute position of q[0] (decode: cache length so far).
    ``kv_len`` masks out cache positions >= kv_len.  Memory is O(Tq * block),
    never O(Tq * Tk) — required for 32k prefill and 500k decode.

    Self-attention of several queries over their own keys from position 0
    (training, prefill), with no traced window flag, a length that is a
    multiple of 128 and one device, runs on a TPU as one fused Pallas flash
    kernel (``kernels.ops.causal_flash_attention``) that visits only the
    causally visible key blocks; everything else, and every platform but
    the TPU, takes the online-softmax block scan.
    """
    Tq, Tk = q.shape[1], k.shape[1]
    if (1 < Tq == Tk and Tq % 128 == 0 and kv_len is None
            and isinstance(q_offset, int) and q_offset == 0
            and (spec.window == 0 or is_global is True) and _one_device()):
        from repro.kernels import ops
        # the kernel's branch is lowered only for a TPU: never interpreted
        kernel = functools.partial(
            ops.causal_flash_attention,
            scale=spec.scale or q.shape[-1] ** -0.5, softcap=spec.softcap,
            interpret=False)
        scan = functools.partial(_block_scan, spec=spec, q_offset=0,
                                 is_global=True, kv_len=None)
        return jax.lax.platform_dependent(q, k, v, tpu=kernel, default=scan)
    return _block_scan(q, k, v, spec=spec, q_offset=q_offset,
                       is_global=is_global, kv_len=kv_len)


def _one_device() -> bool:
    """No mesh of several devices is active: the compiler does not
    partition a Pallas kernel."""
    from repro.sharding.context import current_mesh
    mesh = current_mesh()
    return mesh is None or mesh.size == 1


def _block_scan(q: jax.Array, k: jax.Array, v: jax.Array, *,
                spec: AttnSpec, q_offset: jax.Array | int,
                is_global: jax.Array | bool,
                kv_len: jax.Array | None) -> jax.Array:
    """Online-softmax attention over KV blocks, in float32 (the arguments
    of :func:`attention`)."""
    B, Tq, H, Dh = q.shape
    _, Tk, K, _ = k.shape
    G = H // K
    blk = min(spec.kv_block, Tk)
    nblk = -(-Tk // blk)
    pad = nblk * blk - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = spec.scale or Dh ** -0.5
    qg = (q.astype(jnp.float32) * scale).reshape(B, Tq, K, G, Dh)
    kb = k.reshape(B, nblk, blk, K, Dh).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nblk, blk, K, Dh).transpose(1, 0, 2, 3, 4)
    qpos = (jnp.asarray(q_offset) + jnp.arange(Tq))                  # (Tq,)
    limit = jnp.asarray(Tk if kv_len is None else kv_len)
    glob = jnp.asarray(is_global)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, kstart = inp
        s = jnp.einsum("btkgd,bskd->btkgs", qg,
                       kblk.astype(jnp.float32))                     # B,Tq,K,G,blk
        s = _softcap(s, spec.softcap)
        kpos = kstart + jnp.arange(blk)                              # (blk,)
        delta = qpos[:, None] - kpos[None, :]                        # (Tq, blk)
        ok = (delta >= 0) & (kpos[None, :] < limit)
        if spec.window > 0:
            ok &= glob | (delta < spec.window)
        s = jnp.where(ok[None, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "btkgs,bskd->btkgd", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Tq, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Tq, K, G), jnp.float32)
    a0 = jnp.zeros((B, Tq, K, G, Dh), jnp.float32)
    starts = jnp.arange(nblk) * blk
    # flash-attention backward semantics: recompute the (Tq, blk) score
    # blocks in the VJP instead of saving them — without this the scan
    # stores O(Tq * Tk) fp32 per layer and 32k prefill cannot fit
    body = jax.checkpoint(body)
    (m, lsum, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, starts))
    out = acc / jnp.maximum(lsum, 1e-30)[..., None]
    return out.reshape(B, Tq, H, Dh).astype(q.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     spec: AttnSpec, *, pos: jax.Array | int,
                     is_global: jax.Array | bool = True) -> jax.Array:
    """Attention of one new token per sequence over its cache, read where
    it lies.

    q: (B, 1, H, Dh), the token at position ``pos``; k, v: (B, S, K, Dh),
    the cache with that token's K/V written.  The same numbers as
    :func:`attention` with ``q_offset=pos`` and ``kv_len=pos + 1`` (float32
    scores, softmax and accumulation, the same scale, masks and softcap),
    but the two dots read the cache in its own layout: no block reshape or
    transpose, so the compiler reads it in place.
    """
    B, _, H, Dh = q.shape
    S, K = k.shape[1], k.shape[2]
    scale = spec.scale or Dh ** -0.5
    qg = (q[:, 0].astype(jnp.float32) * scale).reshape(B, K, H // K, Dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32))
    s = _softcap(s, spec.softcap)
    delta = jnp.asarray(pos) - jnp.arange(S)                         # (S,)
    ok = delta >= 0
    if spec.window > 0:
        ok &= jnp.asarray(is_global) | (delta < spec.window)
    s = jnp.where(ok, s, NEG_INF)
    p = jnp.exp(s - s.max(axis=-1, keepdims=True))
    out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    out = out / p.sum(axis=-1)[..., None]
    return out.reshape(B, 1, H, Dh).astype(q.dtype)


def decode_block(spec: AttnSpec, batch: int, max_len: int, dtype) -> int:
    """The cache block that one token's attention reads at a time on a TPU
    (``kernels.ops.paged_decode_attention``), or 0 where
    :func:`decode_attention` reads the whole cache: a mesh of several
    devices, or shapes the kernel does not take."""
    from repro.kernels import ops
    if not _one_device():
        return 0
    return ops.decode_block(batch, max_len, spec.n_kv_heads,
                            spec.n_heads // spec.n_kv_heads, spec.head_dim,
                            jnp.dtype(dtype).itemsize)


def _decode(q, ck, cv, spec: AttnSpec, *, layer, pos, is_global):
    """One new token's attention over the cache (B, S, K, Dh), or over the
    stack of every layer's (L, B, S, K, Dh) at ``layer``.  Where
    :func:`decode_block` gives a block, the program lowered for a TPU
    reads only the blocks up to ``pos``'s, through the Pallas decode
    kernel; elsewhere, and on every other platform, the whole cache
    through :func:`decode_attention`."""
    if layer is None:
        ck, cv, layer = ck[None], cv[None], 0

    def whole(q, ck, cv, layer, pos, is_global):
        return decode_attention(q, ck[layer], cv[layer], spec, pos=pos,
                                is_global=is_global)

    args = (q, ck, cv, jnp.asarray(layer), jnp.asarray(pos),
            jnp.asarray(is_global))
    if not decode_block(spec, q.shape[0], ck.shape[2], ck.dtype):
        return whole(*args)
    from repro.kernels import ops

    def kernel(q, ck, cv, layer, pos, is_global):
        # lowered only for a TPU: never interpreted
        return ops.paged_decode_attention(
            q, ck, cv, layer, pos, scale=spec.scale or q.shape[-1] ** -0.5,
            window=spec.window, softcap=spec.softcap, is_global=is_global,
            interpret=False)
    return jax.lax.platform_dependent(*args, tpu=kernel, default=whole)


def init_attn_params(key, d_model: int, spec: AttnSpec, dtype,
                     qk_norm: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, (spec.n_heads, spec.head_dim), dtype),
        "wk": dense_init(ks[1], d_model, (spec.n_kv_heads, spec.head_dim),
                         dtype),
        "wv": dense_init(ks[2], d_model, (spec.n_kv_heads, spec.head_dim),
                         dtype),
        "wo": dense_init(ks[3], spec.n_heads * spec.head_dim, (d_model,),
                         dtype).reshape(spec.n_heads, spec.head_dim, d_model),
    }
    if qk_norm:
        p["q_norm"] = jnp.zeros((spec.head_dim,), dtype)
        p["k_norm"] = jnp.zeros((spec.head_dim,), dtype)
    return p


@jax.named_scope(ATTENTION)
def attn_block(params: Params, x: jax.Array, spec: AttnSpec, *,
               rope_theta: float, norm_eps: float,
               positions: jax.Array,
               is_global: jax.Array | bool = True,
               kv_cache: tuple[jax.Array, jax.Array] | None = None,
               cache_len: jax.Array | None = None,
               layer: jax.Array | int | None = None,
               xkv: jax.Array | None = None,
               use_rope: bool = True,
               ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Projections + (cached) attention.  Returns (out, (k_all, v_all)).

    * training/prefill: ``kv_cache`` is None -> attends within x.
    * decode: ``kv_cache`` holds (B, S, K, Dh), or the stack of every
      layer's, (L, B, S, K, Dh), of which this is ``layer``; x is the new
      token(s); the cache is updated at ``cache_len`` and returned whole.
      One new token reads it through :func:`_decode` (on a TPU only the
      blocks up to its position); a prompt at a ``cache_len`` of a Python
      0 attends to its own K/V alone.
    * cross-attention: ``xkv`` supplies the key/value source sequence.
    """
    src = x if xkv is None else xkv
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, params["wv"])
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"], 1e-6)
        k = rms_norm(k, params["k_norm"], 1e-6)
    if use_rope:
        q = rope(q, positions, rope_theta)
        kpos = positions if kv_cache is None else positions
        k = rope(k, kpos, rope_theta)

    if kv_cache is not None:
        ck, cv = kv_cache
        pos = cache_len if cache_len is not None else 0
        # the stacked cache is written and read at the layer's index: where
        # the compiler sees a constant there, it updates the cache in place
        # and feeds the layer's slice straight to the dots
        kn, vn, at = k.astype(ck.dtype), v.astype(cv.dtype), ()
        if layer is not None:
            kn, vn, at = kn[None], vn[None], (layer,)
        with jax.named_scope(KV_CACHE):
            ck = jax.lax.dynamic_update_slice(ck, kn, (*at, 0, pos, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, vn, (*at, 0, pos, 0, 0))
        if x.shape[1] == 1:
            out = _decode(q, ck, cv, spec, layer=layer, pos=pos,
                          is_global=is_global)
        elif isinstance(pos, int) and pos == 0:
            # a prompt from position 0 sees only its own keys: attend to
            # the fresh K/V, not to the cache's empty positions
            out = attention(q, k.astype(ck.dtype), v.astype(cv.dtype), spec,
                            is_global=is_global)
        else:
            lk, lv = (ck, cv) if layer is None else (ck[layer], cv[layer])
            out = attention(q, lk, lv, spec, q_offset=pos,
                            is_global=is_global, kv_len=pos + x.shape[1])
        k_all, v_all = ck, cv
    elif xkv is not None:
        # cross-attention: no causal mask — emulate by huge offset
        out = attention(q, k, v, spec, q_offset=src.shape[1],
                        is_global=True)
        k_all, v_all = k, v
    else:
        out = attention(q, k, v, spec, q_offset=0, is_global=is_global)
        k_all, v_all = k, v
    return jnp.einsum("bthk,hkd->btd", out, params["wo"]), (k_all, v_all)


# --- MLP -------------------------------------------------------------------------

def init_mlp_params(key, d_model: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "wi_gate": dense_init(ks[0], d_model, (d_ff,), dtype),
        "wi_up": dense_init(ks[1], d_model, (d_ff,), dtype),
        "wo": dense_init(ks[2], d_ff, (d_model,), dtype),
    }


def _act(x: jax.Array, kind: str) -> jax.Array:
    return jax.nn.silu(x) if kind == "silu" else jax.nn.gelu(x)


@jax.named_scope(MLP)
def mlp_block(params: Params, x: jax.Array, act: str,
              overlap: bool = False) -> jax.Array:
    """(Sw/Ge)GLU FFN.

    With ``overlap=True`` (config.overlap == "shared_bus") and an active
    mesh, the tensor-parallel matmuls run as Shared-PIM-style rings
    (``core.overlap.collective_matmul``): the blocking all-gather /
    reduce-scatter around the two matmuls become double-buffered ppermute
    streams overlapped with the MXU work.
    """
    if overlap:
        from repro.core.overlap.collective_matmul import overlapped_ffn
        from repro.sharding.context import current_mesh
        mesh = current_mesh()
        tp = (dict(zip(mesh.axis_names, mesh.shape.values())).get("model", 1)
              if mesh is not None else 1)
        f = params["wi_gate"].shape[-1]
        if (mesh is not None and tp > 1 and x.shape[1] % tp == 0
                and f % tp == 0):
            return overlapped_ffn(
                x, params["wi_gate"], params["wi_up"], params["wo"], mesh,
                lambda v: _act(v, act))
    g = _act(jnp.einsum("btd,df->btf", x, params["wi_gate"]), act)
    u = jnp.einsum("btd,df->btf", x, params["wi_up"])
    return jnp.einsum("btf,fd->btd", g * u, params["wo"])


# --- cross-attention query mask fix ----------------------------------------------
# (cross attention uses q_offset=len(src) so every source position passes the
# causal test: delta = q_offset + t - kpos >= 0 for all kpos < len(src))


# --- remat policies ---------------------------------------------------------------

def remat_policy(name: str):
    if name == "none":
        return None
    if name == "dots":
        # and the flash kernel's output and log-sum-exp, so that the
        # backward pass does not run the kernel's forward again
        from repro.kernels.ops import FLASH_RESIDUALS
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(FLASH_RESIDUALS))
    if name == "full":
        return jax.checkpoint_policies.nothing_saveable
    raise ValueError(f"unknown remat policy {name!r}")


def maybe_remat(fn, policy_name: str):
    if policy_name == "none":
        return fn
    return jax.checkpoint(fn, policy=remat_policy(policy_name))
