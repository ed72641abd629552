"""Mixture-of-Experts layer: top-k routing with capacity-bounded dispatch.

Dispatch strategy (dry-run- and TPU-friendly — no (N, E, C) one-hot combine
tensors): tokens' (expert, weight) assignments are flattened, sorted by
expert id, and scattered into an (E, C, d) buffer; expert FFNs run as one
grouped einsum; results are gathered back and weight-combined.  Tokens beyond
an expert's capacity are dropped (standard capacity-factor semantics).

Sharding: the (E, C, d) buffers and (E, d, f) weights carry either EP
(experts over 'model') or TP (ffn dim over 'model') shardings, chosen by
``sharding.partition`` based on divisibility — llama4's 128 experts go EP
(8 experts/chip on a 16-way axis, dispatch becomes an all-to-all), qwen2's
60 experts go TP on the 1408-wide ffn.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.models.layers import Params, dense_init


def init_moe_params(key, d_model: int, cfg, dtype) -> Params:
    ks = jax.random.split(key, 5)
    E, f = cfg.n_experts, cfg.moe_d_ff
    p = {
        "router": dense_init(ks[0], d_model, (E,), jnp.float32),
        "wi_gate": dense_init(ks[1], d_model, (E, f), dtype
                              ).transpose(1, 0, 2),   # (E, d, f)
        "wi_up": dense_init(ks[2], d_model, (E, f), dtype).transpose(1, 0, 2),
        "wo": dense_init(ks[3], f, (E, d_model), dtype).transpose(1, 0, 2),
    }
    if cfg.shared_expert_d_ff:
        p["shared"] = layers.init_mlp_params(ks[4], d_model,
                                             cfg.shared_expert_d_ff, dtype)
    return p


@jax.named_scope(layers.MOE)
def moe_block(params: Params, x: jax.Array, cfg, *,
              capacity_factor: float = 1.25) -> jax.Array:
    """x: (B, T, d) -> (B, T, d)."""
    B, T, d = x.shape
    E, k = cfg.n_experts, cfg.n_experts_active
    N = B * T
    xf = x.reshape(N, d)

    logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32),
                        params["router"])
    weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    C = max(1, int(capacity_factor * k * N / E))
    flat_expert = experts.reshape(-1)                       # (N*k,)
    flat_token = jnp.repeat(jnp.arange(N), k)
    flat_weight = weights.reshape(-1)

    order = jnp.argsort(flat_expert)                        # stable
    se, st, sw = (flat_expert[order], flat_token[order], flat_weight[order])
    # position within expert segment
    seg_start = jnp.searchsorted(se, jnp.arange(E), side="left")
    pos_in_e = jnp.arange(N * k) - seg_start[se]
    keep = pos_in_e < C

    # scatter tokens into the (E, C, d) dispatch buffer
    buf = jnp.zeros((E, C, d), x.dtype)
    slot_e = jnp.where(keep, se, 0)
    slot_c = jnp.where(keep, pos_in_e, 0)
    tok = xf[st] * keep[:, None].astype(x.dtype)
    buf = buf.at[slot_e, slot_c].add(tok)

    # grouped expert FFN: (E, C, d) x (E, d, f)
    g = layers._act(jnp.einsum("ecd,edf->ecf", buf, params["wi_gate"]),
                    cfg.act)
    u = jnp.einsum("ecd,edf->ecf", buf, params["wi_up"])
    out_e = jnp.einsum("ecf,efd->ecd", g * u, params["wo"])

    # gather back and combine with routing weights
    gathered = out_e[slot_e, slot_c] * (sw * keep)[:, None].astype(x.dtype)
    combined = jnp.zeros((N, d), x.dtype).at[st].add(gathered)
    out = combined.reshape(B, T, d)

    if "shared" in params:
        out = out + layers.mlp_block(params["shared"], x, cfg.act)
    return out
