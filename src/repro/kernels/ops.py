"""Public jit'd wrappers for the Pallas kernels.

Each op dispatches to the Pallas kernel (interpret-mode on CPU, compiled on
TPU) with model-layer-friendly signatures; ``ref.py`` holds the pure-jnp
oracles the tests compare against.
"""

from __future__ import annotations

import concurrent.futures
import functools

import jax
import jax.numpy as jnp
from jax._src import source_info_util
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu import splash_attention as sa

from repro.kernels import flash_attention as fa
from repro.kernels import lut_matmul as lm
from repro.kernels import mamba_scan as ms


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def lut_matmul(x, codes, lut, **kw):
    kw.setdefault("interpret", _on_cpu())
    return lm.lut_matmul(x, codes, lut, **kw)


def quantize_weights(w):
    return lm.quantize_weights(w)


def gqa_flash_attention(q, k, v, **kw):
    """q: (B, T, H, Dh); k/v: (B, T, K, Dh) -> (B, T, H, Dh).

    Folds (batch, kv-head, group) into the kernel's leading dim.
    """
    kw.setdefault("interpret", _on_cpu())
    B, Tq, H, Dh = q.shape
    _, Tk, K, _ = k.shape
    G = H // K
    qf = q.transpose(0, 2, 1, 3).reshape(B * K, G, Tq, Dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, 1, Tk, Dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, 1, Tk, Dh)
    kf = jnp.broadcast_to(kf, (B * K, G, Tk, Dh))
    vf = jnp.broadcast_to(vf, (B * K, G, Tk, Dh))
    out = fa.flash_attention(qf.reshape(B * K * G, Tq, Dh),
                             kf.reshape(B * K * G, Tk, Dh),
                             vf.reshape(B * K * G, Tk, Dh), **kw)
    return out.reshape(B, K, G, Tq, Dh).transpose(0, 3, 1, 2, 4).reshape(
        B, Tq, H, Dh)


# the causal flash kernel's query and key blocks: the largest of these
# that divides the sequence; its score blocks are at most 512 keys wide, and
# its fused backward's blocks at most 512 (timed on a v5e at the cells'
# shapes, 128 to 1024)
FLASH_BLOCKS = (1024, 512, 256, 128)
FLASH_BLOCK_COMPUTE = 512
# the name of the kernel's saved output and log-sum-exp, which the "dots"
# remat policy keeps (``layers.remat_policy``)
FLASH_RESIDUALS = "flash_residuals"


@functools.cache
def _no_user_frames():
    """A traceback that holds no frame of this checkout: taken on a worker
    thread, whose stack is the standard library's."""
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        return pool.submit(source_info_util.Traceback.get_traceback).result()


@functools.lru_cache(maxsize=None)
def _causal_flash(T: int, G: int, softcap: float, interpret: bool):
    """splash attention's MQA kernel, forward and backward, for G query
    heads over one KV head of T keys: causal, so the blocks above the
    diagonal are never visited."""
    b = next(b for b in FLASH_BLOCKS if T % b == 0)
    c = min(b, FLASH_BLOCK_COMPUTE)
    blocks = sa.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=c,
        block_q_dkv=c, block_kv_dkv=c, block_kv_dkv_compute=c,
        use_fused_bwd_kernel=True)
    mask = sa.MultiHeadMask([sa.CausalMask((T, T))] * G)
    # the block tables are constants, made once, outside any trace
    with jax.ensure_compile_time_eval():
        return sa.make_splash_mqa_single_device(
            mask, block_sizes=blocks, attn_logits_soft_cap=softcap or None,
            residual_checkpoint_name=FLASH_RESIDUALS, interpret=interpret)


def causal_flash_attention(q, k, v, *, scale: float, softcap: float = 0.0,
                           interpret: bool | None = None):
    """Causal GQA self-attention of T queries over their own T keys, from
    position 0, in one fused Pallas kernel with its own backward.

    q: (B, T, H, Dh); k, v: (B, T, K, Dh); T a multiple of 128.  Each KV
    head's query group is the kernel's head axis, so K/V are read once per
    group, never broadcast to every query head; batch and KV head are
    vmapped.  Scores, running max and sum and the output accumulator stay
    on chip; q.k takes the stored operands with float32 accumulation, and
    the soft cap, softmax and value product run in float32.  The score
    scale is applied to q, rounded to its dtype.
    """
    if interpret is None:
        interpret = _on_cpu()
    B, T, H, Dh = q.shape
    K = k.shape[2]
    G = H // K
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qg = qs.reshape(B, T, K, G, Dh).transpose(0, 2, 3, 1, 4)
    kernel = _causal_flash(T, G, float(softcap), interpret)
    # the kernel's body goes into the program with the source lines of its
    # operations, and the compile cache's key keeps them: traced with no
    # frame of this checkout, the key is the same wherever it lies
    with source_info_util.user_context(
            _no_user_frames(),
            name_stack=source_info_util.current_name_stack()):
        out = jax.vmap(jax.vmap(kernel))(qg, k.transpose(0, 2, 1, 3),
                                         v.transpose(0, 2, 1, 3))
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, Dh)


# the decode kernel's cache blocks: the largest of these that divides the
# cache length and keeps one block of K (or V) under DECODE_BLOCK_BYTES
DECODE_BLOCKS = (512, 256, 128)
DECODE_BLOCK_BYTES = 256 * 1024
# VMEM the kernel may hold for its query rows (double-buffered) and output
DECODE_VMEM_ROWS = 6 * 2**20
# a masked score: finite, so that a block whose positions are all masked
# (outside a window) leaves no NaN; the next live block's rescale clears it
_NEG_INF = -1e30


def decode_block(B: int, S: int, K: int, G: int, Dh: int,
                 itemsize: int = 2) -> int:
    """The cache block :func:`paged_decode_attention` reads at a time for B
    rows of an S-position cache of K heads of Dh under G query heads each,
    or 0 where the kernel does not take these shapes."""
    width = K * Dh if Dh % 128 else Dh
    rows = B * K * G * (width + Dh) * itemsize * 2
    if Dh % 8 or rows > DECODE_VMEM_ROWS:
        return 0
    return next((b for b in DECODE_BLOCKS if S % b == 0
                 and b * K * Dh * itemsize <= DECODE_BLOCK_BYTES), 0)


@functools.lru_cache(maxsize=None)
def _paged_decode(B: int, S: int, K: int, G: int, Dh: int, blk: int,
                  window: int, softcap: float, dtype, interpret: bool):
    """The decode kernel for these shapes (see
    :func:`paged_decode_attention`)."""
    lanes = Dh % 128 != 0
    H = K * G
    # the query rows against one block: K * Dh block-diagonal columns over
    # a block of K * Dh rows and ``blk`` positions (positions on lanes), or
    # Dh columns over ``blk * K`` rows, position-major (positions on rows)
    width = K * Dh if lanes else Dh
    shape = (K * Dh, blk) if lanes else (blk * K, Dh)
    score = ((1,), (0,)) if lanes else ((1,), (1,))
    value = ((1,), (1,)) if lanes else ((1,), (0,))
    cols = blk if lanes else blk * K

    def paged_decode(pos_ref, layer_ref, glob_ref, q_ref, k_hbm, v_hbm,
                     o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref):
        pos, layer = pos_ref[0], layer_ref[0]
        # the block that holds pos (a position past the cache reads its
        # last block, as the cache write clamps it there)
        last = jnp.minimum(pos // blk, S // blk - 1)

        def copies(b, j, slot):
            at = (pl.ds(pl.multiple_of(j * blk, blk), blk) if lanes else
                  pl.ds(pl.multiple_of(j * blk * K, blk * K), blk * K))
            at = (layer, b, slice(None), at) if lanes else (layer, b, at)
            return [pltpu.make_async_copy(hbm.at[at], buf.at[slot],
                                          sem.at[i, slot])
                    for i, (hbm, buf) in enumerate(((k_hbm, kbuf),
                                                    (v_hbm, vbuf)))]

        for c in copies(0, 0, 0):
            c.start()
        col = jax.lax.broadcasted_iota(jnp.int32, (H, cols), 1)
        key_pos = col if lanes else col // K
        if not lanes:
            own_head = col % K == jax.lax.broadcasted_iota(
                jnp.int32, (H, cols), 0) // G

        def row(b, t):
            m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
            q = q_ref[b]

            def block(j, t):
                slot = t % 2
                more = j < last

                @pl.when(more | (b + 1 < B))
                def _():
                    for c in copies(jnp.where(more, b, b + 1),
                                    jnp.where(more, j + 1, 0), 1 - slot):
                        c.start()

                for c in copies(b, j, slot):
                    c.wait()
                s = jax.lax.dot_general(q, kbuf[slot], (score, ((), ())),
                                        preferred_element_type=jnp.float32)
                if softcap:
                    s = softcap * jnp.tanh(s / softcap)
                delta = pos - (j * blk + key_pos)
                ok = delta >= 0
                if window:
                    ok &= (glob_ref[0] != 0) | (delta < window)
                if not lanes:
                    ok &= own_head
                s = jnp.where(ok, s, _NEG_INF)
                m_prev = m_ref[...]
                m = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                p = jnp.exp(s - m)
                corr = jnp.exp(m_prev - m)
                l_ref[...] = corr * l_ref[...] + p.sum(axis=1, keepdims=True)
                acc_ref[...] = corr * acc_ref[...] + jax.lax.dot_general(
                    p.astype(dtype), vbuf[slot], (value, ((), ())),
                    preferred_element_type=jnp.float32)
                m_ref[...] = m
                return t + 1

            t = jax.lax.fori_loop(0, last + 1, block, t)
            inv = 1.0 / l_ref[...]
            if lanes:
                # each query head's own KV head: its diagonal block
                for k in range(K):
                    rows = slice(k * G, (k + 1) * G)
                    o_ref[b, rows, :] = (
                        acc_ref[rows, k * Dh:(k + 1) * Dh]
                        * inv[rows]).astype(o_ref.dtype)
            else:
                o_ref[b] = (acc_ref[...] * inv).astype(o_ref.dtype)
            return t

        jax.lax.fori_loop(0, B, row, 0)

    vmem = pltpu.VMEM
    return pl.pallas_call(
        paged_decode,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=vmem),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=vmem),
            scratch_shapes=[vmem((2, *shape), dtype), vmem((2, *shape), dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            vmem((H, 1), jnp.float32),
                            vmem((H, 1), jnp.float32),
                            vmem((H, width), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), dtype),
        name="paged_decode_attention",
        interpret=pltpu.InterpretParams() if interpret else False)


def paged_decode_attention(q, k, v, layer, pos, *, scale: float,
                           window: int = 0, softcap: float = 0.0,
                           is_global=True, interpret: bool | None = None):
    """Attention of one new token per sequence over the stacked cache of
    every layer, of which this is ``layer``, reading only the blocks at or
    below the one that holds ``pos``.

    q: (B, 1, H, Dh), the tokens at position ``pos``; k, v: (L, B, S, K,
    Dh), with those tokens' K/V written.  The kernel reads the cache where
    it lies, in the order the chip keeps it: heads narrower than 128 lanes
    with positions on lanes (each head's keys as Dh rows), wider ones
    position-major; so no layer's cache is copied, sliced or relaid.  One
    call runs every row; each row's live blocks stream through two VMEM
    buffers, the next block's copy in flight while the current one is
    scored.  Scores, running max and sum and the accumulator are float32
    in VMEM; q.k and p.v take bf16 operands with float32 accumulation; the
    score scale is applied to q, rounded to its dtype.  Each KV head's
    query group shares one read of its keys and values; ``pos`` and
    ``layer`` are traced, so one program serves every live length.
    """
    if interpret is None:
        interpret = _on_cpu()
    B, _, H, Dh = q.shape
    L, _, S, K, _ = k.shape
    G = H // K
    blk = decode_block(B, S, K, G, Dh, k.dtype.itemsize)
    lanes = Dh % 128 != 0
    qs = (q[:, 0].astype(jnp.float32) * scale).astype(k.dtype)
    if lanes:
        # block-diagonal: query head k * G + g against KV head k's rows
        qs = jnp.einsum("bkgd,kj->bkgjd", qs.reshape(B, K, G, Dh),
                        jnp.eye(K, dtype=qs.dtype)).reshape(B, H, K * Dh)
        k, v = (c.transpose(0, 1, 3, 4, 2).reshape(L, B, K * Dh, S)
                for c in (k, v))
    else:
        k, v = (c.reshape(L, B, S * K, Dh) for c in (k, v))
    kernel = _paged_decode(B, S, K, G, Dh, blk, int(window), float(softcap),
                           jnp.dtype(k.dtype), interpret)
    scalars = (jnp.asarray(pos, jnp.int32).reshape(1),
               jnp.asarray(layer, jnp.int32).reshape(1),
               jnp.asarray(is_global, jnp.int32).reshape(1))
    with source_info_util.user_context(
            _no_user_frames(),
            name_stack=source_info_util.current_name_stack()):
        out = kernel(*scalars, qs, k, v)
    return out.reshape(B, 1, H, Dh).astype(q.dtype)


def mamba_scan(decay, u, c, **kw):
    kw.setdefault("interpret", _on_cpu())
    return ms.mamba_scan(decay, u, c, **kw)
