"""State-space (Mamba) blocks: Mamba-1 (falcon-mamba) and Mamba-2
(zamba2, granite-4.0-h).

Mamba-1's selective scan runs as a chunked associative scan: within-chunk
``jax.lax.associative_scan`` (parallel, depth log c) and a sequential
``lax.scan`` carrying the state across chunks — O(T/c) sequential steps with
O(B * c * d * n) peak memory, the TPU-friendly middle ground.  Mamba-2's
scalar decay per head lets it run as chunked SSD (:func:`ssd_chunked`):
masked matrix products within a chunk, the state passed between chunks.

Decode is the O(1) recurrent step on carried (conv_state, ssm_state) — the
reason the `long_500k` cell is trivial for SSM families.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import Params, dense_init

CHUNK = 256


def _assoc_combine(a, b):
    # linear recurrence h' = A*h + Bx composes as (A2*A1, A2*b1 + b2)
    return a[0] * b[0], b[0] * a[1] + b[1]


def chunked_selective_scan(decay: jax.Array, inp: jax.Array,
                           h0: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Scan h_t = decay_t * h_{t-1} + inp_t over axis 1 (time).

    decay/inp: (B, T, ...); h0: (B, ...).  Returns (all h, final h).
    (Used for short sequences / tests; the model blocks use the fused
    variant below which never materializes the (B, T, d, n) products.)
    """
    B, T = decay.shape[:2]
    c = min(CHUNK, T)
    nchunks = -(-T // c)
    pad = nchunks * c - T
    if pad:
        decay = jnp.pad(decay, ((0, 0), (0, pad)) + ((0, 0),) *
                        (decay.ndim - 2), constant_values=1.0)
        inp = jnp.pad(inp, ((0, 0), (0, pad)) + ((0, 0),) * (inp.ndim - 2))
    dc = decay.reshape(B, nchunks, c, *decay.shape[2:]).swapaxes(0, 1)
    ic = inp.reshape(B, nchunks, c, *inp.shape[2:]).swapaxes(0, 1)

    def chunk_step(h, xs):
        d, i = xs                                  # (B, c, ...)
        # prepend carry as a virtual step: h_t within chunk
        a, b = jax.lax.associative_scan(_assoc_combine, (d, i), axis=1)
        h_all = a * h[:, None] + b                 # (B, c, ...)
        return h_all[:, -1], h_all

    h_last, h_chunks = jax.lax.scan(chunk_step, h0, (dc, ic))
    h_all = h_chunks.swapaxes(0, 1).reshape(B, nchunks * c, *h0.shape[1:])
    return h_all[:, :T], h_last


def fused_ssm_scan(make_chunk, emit_chunk, small_inputs: tuple,
                   h0: jax.Array, T: int, chunk: int,
                   unroll: bool = False) -> tuple[jax.Array, jax.Array]:
    """Chunked selective scan with LAZY (decay, Bx) construction.

    ``small_inputs`` are (B, T, ...) tensors WITHOUT the state dimension;
    ``make_chunk(*chunk_inputs) -> (decay, inp)`` builds the (B, c, ..., n)
    products for one chunk only, and ``emit_chunk(h_all, *chunk_inputs) ->
    y`` contracts the state away again — so the O(T * d * n) intermediate
    never exists, only O(chunk * d * n): Mamba-1's state is per channel,
    so the (T, d_inner, n) products would be terabytes at 32k.
    """
    B = small_inputs[0].shape[0]
    c = min(chunk, T)
    nchunks = -(-T // c)
    pad = nchunks * c - T

    def prep(x):
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape(B, nchunks, c, *x.shape[2:]).swapaxes(0, 1)

    xs = tuple(prep(x) for x in small_inputs)

    def chunk_step(h, chunk_inputs):
        decay, inp = make_chunk(*chunk_inputs)     # (B, c, ..., n)
        a, b = jax.lax.associative_scan(_assoc_combine, (decay, inp),
                                        axis=1)
        h_all = a * h[:, None] + b
        y = emit_chunk(h_all, *chunk_inputs)       # state contracted away
        return h_all[:, -1], y

    # recompute the (B, c, d, n) products in the VJP instead of saving them
    # per chunk (they dominate backward memory otherwise)
    chunk_step = jax.checkpoint(chunk_step)
    # unroll=True for dry-run cost probes (scan bodies are counted once)
    h_last, y_chunks = jax.lax.scan(chunk_step, h0, xs,
                                    unroll=True if unroll else 1)
    y = y_chunks.swapaxes(0, 1).reshape(B, nchunks * c, *y_chunks.shape[3:])
    return y[:, :T], h_last


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array,
                  state: jax.Array | None = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv.  x: (B, T, D); w: (K, D); state: (B, K-1, D)."""
    K = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], K - 1, x.shape[2]), x.dtype)
    xin = jnp.concatenate([state, x], axis=1)
    out = sum(xin[:, i:i + x.shape[1]] * w[i] for i in range(K))
    return out + b, xin[:, -(K - 1):]


def init_mamba_params(key, cfg, dtype) -> Params:
    d, di, n = cfg.d_model, cfg.d_inner, cfg.ssm_state
    ks = jax.random.split(key, 8)
    if cfg.mamba_version == 1:
        dt_rank = max(1, d // 16)
        return {
            "in_proj": dense_init(ks[0], d, (2 * di,), dtype),
            "conv_w": dense_init(ks[1], cfg.ssm_conv, (di,), dtype
                                 ).reshape(cfg.ssm_conv, di),
            "conv_b": jnp.zeros((di,), dtype),
            "out_proj": dense_init(ks[5], di, (d,), dtype),
            "x_proj": dense_init(ks[2], di, (dt_rank + 2 * n,), dtype),
            "dt_proj": dense_init(ks[3], dt_rank, (di,), jnp.float32),
            "dt_bias": jnp.zeros((di,), jnp.float32),
            "A_log": jnp.log(jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32),
                                      (di, 1))),            # (di, n)
            "D": jnp.ones((di,), jnp.float32),
        }
    # mamba2: one input projection to z | xBC | dt, a scalar decay per head
    H, conv = di // cfg.ssm_head_dim, conv_width(cfg)
    return {
        "in_proj": dense_init(ks[0], d, (di + conv + H,), dtype),
        "conv_w": dense_init(ks[1], cfg.ssm_conv, (conv,), dtype
                             ).reshape(cfg.ssm_conv, conv),
        "conv_b": jnp.zeros((conv,), dtype),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "A_log": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "norm_w": jnp.zeros((di,), dtype),
        "out_proj": dense_init(ks[5], di, (d,), dtype),
    }


def conv_width(cfg) -> int:
    """Channels of the causal conv: x for mamba1; x, B and C for mamba2."""
    if cfg.mamba_version == 1:
        return cfg.d_inner
    return cfg.d_inner + 2 * cfg.ssm_groups * cfg.ssm_state


def mamba1_block(p: Params, x: jax.Array, cfg, *,
                 state: tuple[jax.Array, jax.Array] | None = None
                 ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Falcon-mamba style Mamba-1 mixer.  x: (B, T, d)."""
    di, n = cfg.d_inner, cfg.ssm_state
    dt_rank = max(1, cfg.d_model // 16)
    conv_state, h0 = state if state is not None else (None, None)

    xz = jnp.einsum("btd,de->bte", x, p["in_proj"])
    xs, z = jnp.split(xz, 2, axis=-1)
    xs, conv_state = causal_conv1d(xs, p["conv_w"], p["conv_b"], conv_state)
    xs = jax.nn.silu(xs)

    proj = jnp.einsum("bti,ie->bte", xs, p["x_proj"])
    dt_in, Bc, Cc = jnp.split(proj, [dt_rank, dt_rank + n], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("btr,ri->bti", dt_in.astype(jnp.float32), p["dt_proj"])
        + p["dt_bias"])                                       # (B,T,di)
    A = -jnp.exp(p["A_log"])                                  # (di, n)
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], di, n), jnp.float32)

    def make_chunk(dt_c, x_c, b_c, _c_c):
        decay = jnp.exp(dt_c[..., None] * A)                  # (B,c,di,n)
        bx = (dt_c * x_c.astype(jnp.float32))[..., None] \
            * b_c.astype(jnp.float32)[..., None, :]
        return decay, bx

    def emit_chunk(h_all, _dt, _x, _b, c_c):
        return jnp.einsum("bcin,bcn->bci", h_all,
                          c_c.astype(jnp.float32))

    y, h_last = fused_ssm_scan(make_chunk, emit_chunk,
                               (dt, xs, Bc, Cc), h0, x.shape[1], CHUNK,
                               unroll=cfg.unroll_layers)
    y = y + p["D"] * xs.astype(jnp.float32)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return jnp.einsum("bti,id->btd", y, p["out_proj"]), (conv_state, h_last)


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, h0: jax.Array, chunk: int, *, emit=None,
                extra: tuple = (), unroll: bool = False
                ) -> tuple[jax.Array, jax.Array]:
    """Mamba-2's scan as chunked state-space duality (SSD).

    Per head h of group g: S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T and
    y_t = S_t C_t, with x: (Bt, T, H, P), dt: (Bt, T, H) float32, A: (H,),
    B, C: (Bt, T, G, N), h0: (Bt, H, P, N) float32.  Within a chunk of
    ``chunk`` steps the outputs are masked matrix products,
    y_t = sum_{s<=t} exp(a_t - a_s) (C_t . B_s) dt_s x_s + exp(a_t) C_t S_0
    with a the running sum of dt A, and only the state passes from chunk
    to chunk, in float32: nothing of size (T, P, N) is made.  A step past
    the end (padding) has dt = 0 and x = 0, so it leaves the state as it
    is.  Returns (y float32 (Bt, T, H, P), final state); with ``emit``,
    (what ``emit(y, x, *extra)`` makes of each chunk, joined over T, final
    state), the ``extra`` inputs (Bt, T, ...) cut into chunks as x is:
    what follows the scan then runs a chunk at a time too.
    """
    Bt, T, H, P = x.shape
    G = B.shape[2]
    c = min(chunk, T)
    nc = -(-T // c)
    pad = nc * c - T

    def prep(v):
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return v.reshape(Bt, nc, c, *v.shape[2:]).swapaxes(0, 1)

    # heads of one group side by side: (.., G, H/G, ..)
    xs = prep(x.reshape(Bt, T, G, H // G, P))
    extra = tuple(prep(e) for e in extra)
    dts = prep(dt.reshape(Bt, T, G, H // G))
    Bs, Cs = prep(B.astype(jnp.float32)), prep(C.astype(jnp.float32))
    Ag = A.reshape(G, H // G)
    causal = jnp.tril(jnp.ones((c, c), bool))

    def step(S, inp):
        xin, dtc, bc, cc, *more = inp              # (Bt, c, G, Hg, ...)
        xc = xin.astype(jnp.float32)
        dth = dtc.transpose(0, 2, 3, 1)            # (Bt, G, Hg, c)
        a = jnp.cumsum(dth * Ag[..., None], axis=-1)
        seg = a[..., :, None] - a[..., None, :]    # (Bt, G, Hg, t, s)
        L = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        cb = jnp.einsum("btgn,bsgn->bgts", cc, bc)
        w = L * cb[:, :, None] * dth[..., None, :]
        y = jnp.einsum("bghts,bsghp->btghp", w, xc)
        y = y + jnp.einsum("btgn,bghpn->btghp", cc, S) * \
            jnp.exp(a).transpose(0, 3, 1, 2)[..., None]
        to_end = jnp.exp(a[..., -1:] - a) * dth    # (Bt, G, Hg, s)
        S = S * jnp.exp(a[..., -1])[..., None, None] + jnp.einsum(
            "bghs,bsghp,bsgn->bghpn", to_end, xc, bc)
        y = y.reshape(Bt, c, H, P)
        if emit is not None:
            y = emit(y, xin.reshape(Bt, c, H, P), *more)
        return S, y

    S, ys = jax.lax.scan(step, h0.reshape(Bt, G, H // G, P, -1),
                         (xs, dts, Bs, Cs, *extra),
                         unroll=True if unroll else 1)
    y = ys.swapaxes(0, 1).reshape(Bt, nc * c, *ys.shape[3:])[:, :T]
    return y, S.reshape(h0.shape)


def ssd_step(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
             C: jax.Array, h0: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One step of the same recurrence (T = 1), the decode form."""
    Bt, _, H, P = x.shape
    G = B.shape[2]
    rep = lambda v: jnp.repeat(v[:, 0].astype(jnp.float32), H // G, 1)
    Bh, Ch = rep(B), rep(C)                                  # (Bt, H, N)
    dt0 = dt[:, 0]                                           # (Bt, H)
    S = (h0 * jnp.exp(dt0 * A)[..., None, None]
         + (dt0[..., None] * x[:, 0].astype(jnp.float32))[..., None]
         * Bh[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", S, Ch)
    return y[:, None], S


def mamba2_block(p: Params, x: jax.Array, cfg, *,
                 state: tuple[jax.Array, jax.Array] | None = None,
                 mask: jax.Array | None = None
                 ) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Mamba-2 mixer in its published form.  x: (B, T, d).

    ``in_proj`` gives z (d_inner), xBC (d_inner + 2 G N) and dt (H); a
    depthwise causal conv with SiLU runs over xBC, which then splits into
    x (H heads of ``ssm_head_dim``), B and C (G groups of N); dt =
    softplus(dt + dt_bias) and A = -exp(A_log) per head; the SSD scan;
    y + D x; the gated RMSNorm RMSNorm(y * silu(z)) over each group's
    channels; ``out_proj``.  State: (conv window (B, K-1, xBC),
    SSM state (B, H, head_dim, N) float32).  ``mask`` (B, T), false at
    padded positions: there xBC and dt are zero, so a padded position
    before the prompt leaves both states as they were.
    """
    from repro.models.layers import rms_norm
    di, n, P, G = cfg.d_inner, cfg.ssm_state, cfg.ssm_head_dim, cfg.ssm_groups
    H = di // P
    Bt, T, _ = x.shape
    conv_state, h0 = state if state is not None else (None, None)

    zxbcdt = jnp.einsum("btd,de->bte", x, p["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [di, di + conv_width(cfg)], axis=-1)
    if mask is not None:
        xbc = jnp.where(mask[..., None], xbc, jnp.zeros((), xbc.dtype))
    xbc, conv_state = causal_conv1d(xbc, p["conv_w"], p["conv_b"],
                                    conv_state)
    xbc = jax.nn.silu(xbc)
    xs, Bm, Cm = jnp.split(xbc, [di, di + G * n], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])   # (B,T,H)
    if mask is not None:
        dt = jnp.where(mask[..., None], dt, 0.0)
    A = -jnp.exp(p["A_log"])
    xh = xs.reshape(Bt, T, H, P)
    Bm, Cm = Bm.reshape(Bt, T, G, n), Cm.reshape(Bt, T, G, n)
    if h0 is None:
        h0 = jnp.zeros((Bt, H, P, n), jnp.float32)

    def gated_norm(y, xh, z):
        """y + D x, then RMSNorm(y * silu(z)) per group, in x's dtype."""
        t = y.shape[1]
        y = y + p["D"][:, None] * xh.astype(jnp.float32)
        g = (y.reshape(Bt, t, G, di // G)
             * jax.nn.silu(z.astype(jnp.float32)).reshape(Bt, t, G, -1))
        y = rms_norm(g, p["norm_w"].reshape(G, -1), cfg.norm_eps)
        return y.reshape(Bt, t, di).astype(x.dtype)

    if T == 1:
        y, h_last = ssd_step(xh, dt, A, Bm, Cm, h0)
        y = gated_norm(y, xh, z)
    else:
        # the norm a chunk at a time: no float32 (T, d_inner) is made
        y, h_last = ssd_chunked(xh, dt, A, Bm, Cm, h0, cfg.ssm_chunk,
                                emit=gated_norm, extra=(z,),
                                unroll=cfg.unroll_layers)
    return jnp.einsum("bti,id->btd", y, p["out_proj"]), (conv_state, h_last)
