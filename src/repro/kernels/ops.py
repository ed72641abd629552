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


def mamba_scan(decay, u, c, **kw):
    kw.setdefault("interpret", _on_cpu())
    return ms.mamba_scan(decay, u, c, **kw)
