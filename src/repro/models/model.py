"""Config-driven model assembly for all ten assigned architectures.

``build(cfg)`` returns a ``Model`` with:

* ``init(key)``                      -> params pytree (stacked layers for scan)
* ``forward(params, batch)``         -> logits (training / prefill path)
* ``train_loss(params, batch)``      -> scalar LM loss
* ``init_cache(B)``                  -> decode cache pytree (KV / SSM states)
* ``decode_step(params, cache, tok)``-> (logits, cache)  [one-token serve step]

Layer stacks are scanned (``jax.lax.scan`` over stacked params) so the HLO
stays compact for the 512-device dry-run; heterogeneous schedules (gemma
local/global, zamba2 shared attention, llama-vision cross blocks) are
expressed as scanned per-layer flags or group-structured scans.  The
unrolled towers are the ``decode_step`` of the decoder stack and of the
hybrids: there each layer updates and reads its part of the stacked cache
(KV, or a mamba layer's conv window and SSM state) in place, at a constant
index.  The hybrids' other passes scan their schedule of layer kinds
(granite-4.0-h's ``layer_types``, zamba2's shared blocks) with a
``lax.switch`` per step.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers, moe, ssm
from repro.models.layers import AttnSpec, Params


def _stack_init(fn, key, n: int):
    """vmap an init function over n layer keys -> stacked params."""
    return jax.vmap(fn)(jax.random.split(key, n))


def _take(tree, i):
    return jax.tree.map(lambda x: jax.lax.dynamic_index_in_dim(
        x, i, keepdims=False), tree)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ---------------- parameter init ----------------

    def init(self, key) -> Params:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        keys = jax.random.split(key, 8)
        p: Params = {
            "embed": (jax.random.normal(keys[0],
                                        (cfg.vocab_size, cfg.d_model),
                                        jnp.float32) * 0.02).astype(dtype),
            "final_norm": jnp.zeros((cfg.d_model,), dtype),
        }
        if not cfg.tie_embeddings:
            p["unembed"] = layers.dense_init(keys[1], cfg.d_model,
                                             (cfg.vocab_size,), dtype)
        if cfg.family in ("dense", "moe", "audio", "vlm"):
            if cfg.family == "moe" and cfg.moe_every > 1:
                n_moe = cfg.n_layers // cfg.moe_every
                p["blocks"] = _stack_init(
                    lambda k: self._init_block(k, dtype, kind="dense"),
                    keys[2], cfg.n_layers - n_moe)
                p["moe_blocks"] = _stack_init(
                    lambda k: self._init_block(k, dtype, kind="moe"),
                    keys[5], n_moe)
            else:
                p["blocks"] = _stack_init(
                    lambda k: self._init_block(k, dtype), keys[2],
                    cfg.n_layers)
        if cfg.family == "vlm":
            n_cross = cfg.n_layers // cfg.cross_attn_every
            p["cross_blocks"] = _stack_init(
                lambda k: self._init_cross_block(k, dtype), keys[3], n_cross)
            p["media_proj"] = layers.dense_init(
                keys[4], cfg.media_embed_dim, (cfg.d_model,), dtype)
        if cfg.family == "audio":
            p["media_proj"] = layers.dense_init(
                keys[4], cfg.media_embed_dim, (cfg.d_model,), dtype)
        if cfg.family == "ssm":
            p["blocks"] = _stack_init(
                lambda k: self._init_ssm_block(k, dtype), keys[2],
                cfg.n_layers)
        if cfg.family == "hybrid" and cfg.layer_types:
            # mamba layers and attention layers, each kind stacked in
            # layer order; each carries its MLP
            n_attn = cfg.layer_types.count("attention")
            p["blocks"] = _stack_init(
                lambda k: self._init_ssm_block(k, dtype), keys[2],
                cfg.n_layers - n_attn)
            p["attn_blocks"] = _stack_init(
                lambda k: self._init_block(k, dtype), keys[3], n_attn)
        elif cfg.family == "hybrid":
            p["blocks"] = _stack_init(
                lambda k: self._init_ssm_block(k, dtype), keys[2],
                cfg.n_layers)
            p["shared_attn"] = _stack_init(
                lambda k: self._init_shared_attn(k, dtype), keys[3],
                cfg.n_shared_attn_blocks)
        return p

    # per-family sub-inits -------------------------------------------------


    def _scan(self, f, init, xs):
        """lax.scan over stacked layers; fully unrolled when the config asks
        (dry-run cost probes — XLA cost_analysis counts while bodies once)."""
        return jax.lax.scan(f, init, xs,
                            unroll=True if self.cfg.unroll_layers else 1)

    def _attn_spec(self) -> AttnSpec:
        cfg = self.cfg
        return AttnSpec(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                        window=cfg.sliding_window,
                        softcap=cfg.attn_logit_softcap,
                        kv_block=cfg.attn_kv_block,
                        scale=cfg.attention_multiplier)

    def _init_block(self, key, dtype, kind: str | None = None) -> Params:
        cfg = self.cfg
        if kind is None:
            kind = "moe" if cfg.family == "moe" else "dense"
        ks = jax.random.split(key, 4)
        p = {
            "ln1": jnp.zeros((cfg.d_model,), dtype),
            "attn": layers.init_attn_params(ks[0], cfg.d_model,
                                            self._attn_spec(), dtype,
                                            qk_norm=cfg.qk_norm),
            "ln2": jnp.zeros((cfg.d_model,), dtype),
        }
        if kind == "moe":
            p["moe"] = moe.init_moe_params(ks[1], cfg.d_model, cfg, dtype)
        else:
            p["mlp"] = layers.init_mlp_params(ks[1], cfg.d_model, cfg.d_ff,
                                              dtype)
        return p

    def _init_cross_block(self, key, dtype) -> Params:
        cfg = self.cfg
        ks = jax.random.split(key, 2)
        return {
            "ln": jnp.zeros((cfg.d_model,), dtype),
            "attn": layers.init_attn_params(ks[0], cfg.d_model,
                                            self._attn_spec(), dtype),
            "gate": jnp.zeros((), jnp.float32),
        }

    def _init_shared_attn(self, key, dtype) -> Params:
        # zamba2 shared block = attention + MLP (the mamba layers themselves
        # carry no MLP; published total ~2.7B checks out only this way)
        cfg = self.cfg
        ks = jax.random.split(key, 2)
        return {
            "ln": jnp.zeros((cfg.d_model,), dtype),
            "attn": layers.init_attn_params(ks[0], cfg.d_model,
                                            self._attn_spec(), dtype),
            "ln2": jnp.zeros((cfg.d_model,), dtype),
            "mlp": layers.init_mlp_params(ks[1], cfg.d_model, cfg.d_ff,
                                          dtype),
        }

    def _init_ssm_block(self, key, dtype) -> Params:
        cfg = self.cfg
        ks = jax.random.split(key, 2)
        p = {"ln": jnp.zeros((cfg.d_model,), dtype),
             "mixer": ssm.init_mamba_params(ks[0], cfg, dtype)}
        if cfg.layer_types:
            p["ln2"] = jnp.zeros((cfg.d_model,), dtype)
            p["mlp"] = layers.init_mlp_params(ks[1], cfg.d_model, cfg.d_ff,
                                              dtype)
        return p

    # ---------------- per-layer flags ----------------

    def _layer_is_global(self) -> jax.Array:
        cfg = self.cfg
        if cfg.sliding_window and cfg.local_global_every:
            idx = jnp.arange(cfg.n_layers)
            return (idx % cfg.local_global_every) == (
                cfg.local_global_every - 1)
        return jnp.ones((cfg.n_layers,), bool)

    # ---------------- forward (train / prefill) ----------------

    def _scale_embedding(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        if cfg.embedding_multiplier:
            return x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
        if cfg.family == "audio" or (cfg.family == "dense"
                                     and cfg.tie_embeddings):
            return x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        return x

    def _residual(self, y: jax.Array) -> jax.Array:
        """A block's output as it joins the residual stream."""
        m = self.cfg.residual_multiplier
        return y if m == 1.0 else y * jnp.asarray(m, y.dtype)

    @jax.named_scope(layers.EMBED)
    def embed_inputs(self, params: Params, batch: dict) -> jax.Array:
        cfg = self.cfg
        x = self._scale_embedding(params["embed"][batch["tokens"]])
        if cfg.family == "audio":
            media = jnp.einsum("bmd,dk->bmk", batch["media"].astype(x.dtype),
                               params["media_proj"])
            x = jnp.concatenate([media, x], axis=1)
        return x

    def forward(self, params: Params, batch: dict) -> jax.Array:
        cfg = self.cfg
        x = self.embed_inputs(params, batch)
        B, T, _ = x.shape
        positions = jnp.arange(T)[None, :].repeat(B, 0)
        if cfg.family in ("dense", "moe", "audio"):
            x = self._run_decoder(params, x, positions)
        elif cfg.family == "vlm":
            x = self._run_vlm(params, x, positions,
                              batch["media"])
        elif cfg.family == "ssm":
            x = self._run_ssm(params, x)
        elif cfg.family == "hybrid":
            x, _ = self._hybrid_scan(params, None, x, positions)
        x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        if cfg.family == "audio":
            x = x[:, cfg.n_media_tokens:]           # strip conditioning frames
        logits = self._unembed(params, x)
        return logits

    @jax.named_scope(layers.UNEMBED)
    def _unembed(self, params: Params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        logits = jnp.einsum("btd,dv->btv", x, w.astype(x.dtype))
        if cfg.logits_scaling != 1.0:
            logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
        if cfg.final_logit_softcap:
            logits = (cfg.final_logit_softcap
                      * jnp.tanh(logits / cfg.final_logit_softcap))
        return logits

    def _constrain_residual(self, x):
        """Optionally pin the residual stream to pure-DP sharding at layer
        boundaries so GSPMD gathers weights instead of resharding
        activations (§Perf iteration; config.constrain_activations)."""
        if not self.cfg.constrain_activations:
            return x
        from repro.sharding.context import constrain
        return constrain(x, ("pod", "data"), None, None)

    def _decoder_layer(self, blk: Params, x, positions, is_global,
                       kv_cache=None, cache_len=None, layer=None):
        cfg = self.cfg
        spec = self._attn_spec()
        x = self._constrain_residual(x)
        h = layers.rms_norm(x, blk["ln1"], cfg.norm_eps)
        a, kv = layers.attn_block(
            blk["attn"], h, spec, rope_theta=cfg.rope_theta,
            norm_eps=cfg.norm_eps, positions=positions, is_global=is_global,
            kv_cache=kv_cache, cache_len=cache_len, layer=layer,
            use_rope=cfg.family != "audio"
            and cfg.position_embedding == "rope",
            constrain_dp=cfg.constrain_internals)
        x = x + self._residual(a)
        h = layers.rms_norm(x, blk["ln2"], cfg.norm_eps)
        if "moe" in blk:
            x = x + moe.moe_block(blk["moe"], h, cfg)
        else:
            x = x + self._residual(layers.mlp_block(
                blk["mlp"], h, cfg.act, overlap=cfg.overlap == "shared_bus",
                constrain_dp=cfg.constrain_internals))
        return x, kv

    def _run_decoder(self, params, x, positions):
        cfg = self.cfg
        flags = self._layer_is_global()

        if "moe_blocks" in params:
            # llama4-style interleave: groups of (moe_every-1 dense + 1 moe)
            k = cfg.moe_every - 1
            n_groups = cfg.n_layers // cfg.moe_every
            dense = jax.tree.map(
                lambda a: a.reshape(n_groups, k, *a.shape[1:]),
                params["blocks"])

            def group(x, inp):
                dgrp, mblk = inp

                def inner(x, blk):
                    x, _ = self._decoder_layer(blk, x, positions, True)
                    return x, None

                x, _ = self._scan(inner, x, dgrp)
                x, _ = self._decoder_layer(mblk, x, positions, True)
                return x, None

            group = layers.maybe_remat(group, cfg.remat_policy)
            x, _ = self._scan(group, x, (dense, params["moe_blocks"]))
            return x

        def layer(x, inp):
            blk, is_global = inp
            x, _ = self._decoder_layer(blk, x, positions, is_global)
            return x, None

        layer = layers.maybe_remat(layer, cfg.remat_policy)
        x, _ = self._scan(layer, x, (params["blocks"], flags))
        return x

    def _run_vlm(self, params, x, positions, media):
        cfg = self.cfg
        mtok = jnp.einsum("bmd,dk->bmk", media.astype(x.dtype),
                          params["media_proj"])
        k = cfg.cross_attn_every
        n_groups = cfg.n_layers // k
        blocks = jax.tree.map(
            lambda a: a.reshape(n_groups, k, *a.shape[1:]), params["blocks"])
        flags = self._layer_is_global().reshape(n_groups, k)

        def group(x, inp):
            grp, cross, fl = inp

            def self_layer(x, inner):
                blk, g = inner
                x, _ = self._decoder_layer(blk, x, positions, g)
                return x, None

            x, _ = self._scan(self_layer, x, (grp, fl))
            # gated cross-attention into the (stub) vision tokens
            h = layers.rms_norm(x, cross["ln"], cfg.norm_eps)
            a, _ = layers.attn_block(
                cross["attn"], h, self._attn_spec(),
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                positions=positions, xkv=mtok, use_rope=False)
            x = x + jnp.tanh(cross["gate"]).astype(x.dtype) * a
            return x, None

        group = layers.maybe_remat(group, cfg.remat_policy)
        x, _ = self._scan(group, x, (blocks, params["cross_blocks"], flags))
        return x

    @jax.named_scope(layers.SSM)
    def _ssm_layer(self, blk, x, state=None, mask=None):
        cfg = self.cfg
        x = self._constrain_residual(x)
        h = layers.rms_norm(x, blk["ln"], cfg.norm_eps)
        if cfg.mamba_version == 1:
            y, new_state = ssm.mamba1_block(blk["mixer"], h, cfg, state=state)
        else:
            y, new_state = ssm.mamba2_block(blk["mixer"], h, cfg,
                                            state=state, mask=mask)
        return x + self._residual(y), new_state

    def _run_ssm(self, params, x):
        def layer(x, blk):
            x, _ = self._ssm_layer(blk, x)
            return x, None

        layer = layers.maybe_remat(layer, self.cfg.remat_policy)
        x, _ = self._scan(layer, x, params["blocks"])
        return x

    def _mamba_layer(self, blk, x, state=None, mask=None):
        """One mamba layer, and for granite-4.0-h the MLP after it.
        ``state``: its (conv window, SSM state), or None (no cache)."""
        cfg = self.cfg
        x, state = self._ssm_layer(blk, x, state=state, mask=mask)
        if "mlp" in blk:
            hn = layers.rms_norm(x, blk["ln2"], cfg.norm_eps)
            x = x + self._residual(layers.mlp_block(
                blk["mlp"], hn, cfg.act, overlap=cfg.overlap == "shared_bus"))
        return x, state

    def _mamba_decode_layer(self, blk, x, conv, h, layer):
        """``_mamba_layer`` over every mamba layer's stacked conv windows
        and SSM states, of which this layer's are at ``layer``: read there
        and written back there, in place where the compiler sees a
        constant index."""
        x, (nc, nh) = self._mamba_layer(blk, x, (conv[layer], h[layer]))
        with jax.named_scope(layers.SSM):
            conv = jax.lax.dynamic_update_index_in_dim(
                conv, nc.astype(conv.dtype), layer, 0)
            h = jax.lax.dynamic_update_index_in_dim(h, nh, layer, 0)
        return x, conv, h

    def _attn_decode_layer(self, blk, x, positions, k, v, pos, layer):
        """One attention layer over the stacked K/V caches at ``layer``."""
        x, (k, v) = self._decoder_layer(blk, x, positions, True,
                                        kv_cache=(k, v), cache_len=pos,
                                        layer=layer)
        return x, k, v

    def _hybrid_schedule(self) -> list[tuple[str, int]]:
        """The hybrid stack in order: (kind, index in its weight stack).
        granite-4.0-h: each layer's mixer, "mamba" or "attention";
        zamba2: every mamba layer, and after each ``attn_every``-th one
        a "shared" block (its cache index is its place among them)."""
        cfg = self.cfg
        if cfg.layer_types:
            seen = {"mamba": 0, "attention": 0}
            out = []
            for kind in cfg.layer_types:
                out.append((kind, seen[kind]))
                seen[kind] += 1
            return out
        out = []
        for i in range(cfg.n_layers):
            out.append(("mamba", i))
            if i % cfg.attn_every == cfg.attn_every - 1:
                out.append(("shared", i // cfg.attn_every))
        return out

    def _hybrid_block(self, params, kind, i):
        """The weights of one step of the hybrid schedule (``i`` may be
        traced)."""
        if kind == "mamba":
            return _take(params["blocks"], i)
        if kind == "attention":
            return _take(params["attn_blocks"], i)
        # zamba2's shared block, cycled over its weight sets
        sa = _take(params["shared_attn"], i % self.cfg.n_shared_attn_blocks)
        return {**sa, "ln1": sa["ln"]}

    def _hybrid_scan(self, params, cache, x, positions, mask=None):
        """Prefill and training over a hybrid stack: one scan over the
        schedule, each step the layer of its kind (``lax.switch``) with its
        weights at the step's index.  With a cache, a step reads its
        layer's slots (a mamba layer's conv window and SSM state, an
        attention layer's K/V) out of the stacked caches, the switch
        computes on those slots alone, and the step writes them back in
        place; a step of the other kind writes back what it read.  So the
        stacks, carried by the scan, never enter a branch, where the one
        that leaves a stack unchanged would copy it whole.  Returns
        (x, cache); without a cache nothing is cached."""
        cfg = self.cfg
        steps = self._hybrid_schedule()
        kinds = [k for k in ("mamba", "attention", "shared")
                 if any(kind == k for kind, _ in steps)]
        cached = cache is not None
        # prefill starts at a Python 0: attention reads the prompt's own K/V
        pos = 0 if cached else None

        def mamba(x, slots, i):
            conv, h, k, v = slots
            blk = self._hybrid_block(params, "mamba", i)
            x, state = self._mamba_layer(blk, x, (conv, h) if cached
                                         else None, mask)
            return x, (*state, k, v) if cached else slots

        def attention(kind):
            def run(x, slots, i):
                conv, h, k, v = slots
                x, kv = self._decoder_layer(
                    self._hybrid_block(params, kind, i), x, positions, True,
                    kv_cache=(k, v) if cached else None, cache_len=pos)
                return x, (conv, h, *kv) if cached else slots
            return run

        branches = [mamba if k == "mamba" else attention(k) for k in kinds]

        def layer(carry, step):
            x, stacks = carry
            kind, i, at = step                     # at: (mamba, KV) slot
            slots = ((stacks[0][at[0]], stacks[1][at[0]], stacks[2][at[1]],
                      stacks[3][at[1]]) if cached else (None,) * 4)
            x, slots = jax.lax.switch(kind, branches, x, slots, i)
            if cached:
                conv, h, k, v = stacks
                with jax.named_scope(layers.SSM):
                    conv = jax.lax.dynamic_update_index_in_dim(
                        conv, slots[0].astype(conv.dtype), at[0], 0)
                    h = jax.lax.dynamic_update_index_in_dim(
                        h, slots[1], at[0], 0)
                with jax.named_scope(layers.ATTENTION), \
                        jax.named_scope(layers.KV_CACHE):
                    k = jax.lax.dynamic_update_index_in_dim(
                        k, slots[2], at[1], 0)
                    v = jax.lax.dynamic_update_index_in_dim(
                        v, slots[3], at[1], 0)
                stacks = (conv, h, k, v)
            return (x, stacks), None

        # each step's slots: its own, and for the other kind the last seen
        at, last = [], {"mamba": 0, "kv": 0}
        for kind, i in steps:
            last["mamba" if kind == "mamba" else "kv"] = i
            at.append((last["mamba"], last["kv"]))
        sched = (jnp.asarray([kinds.index(k) for k, _ in steps]),
                 jnp.asarray([i for _, i in steps]), jnp.asarray(at))
        stacks = (tuple(cache[n] for n in ("conv", "h", "k", "v"))
                  if cached else None)
        layer = layers.maybe_remat(layer, cfg.remat_policy)
        (x, stacks), _ = self._scan(layer, (x, stacks), sched)
        if cached:
            cache = {**cache, **dict(zip(("conv", "h", "k", "v"), stacks))}
        return x, cache

    def _hybrid_decode(self, params, cache, x, positions):
        """One decode step through a hybrid stack, unrolled: each layer is
        one call of a jitted function traced once per kind, which reads
        and writes its own slot of the stacked cache at an index the
        compiler sees as a constant, in place.  No layer's state is sliced
        out as a scan's xs or stacked again as its ys."""
        mamba = jax.jit(self._mamba_decode_layer)
        attn = jax.jit(self._attn_decode_layer)
        conv, h, k, v = (cache[n] for n in ("conv", "h", "k", "v"))
        for kind, i in self._hybrid_schedule():
            blk = self._hybrid_block(params, kind, i)
            if kind == "mamba":
                x, conv, h = mamba(blk, x, conv, h, jnp.int32(i))
            else:
                x, k, v = attn(blk, x, positions, k, v, cache["pos"],
                               jnp.int32(i))
        return x, {**cache, "conv": conv, "h": h, "k": k, "v": v}

    # ---------------- loss ----------------

    def train_loss(self, params: Params, batch: dict) -> jax.Array:
        from repro.sharding.context import constrain
        logits = self.forward(params, batch)
        with jax.named_scope(layers.LOSS):
            # keep the vocab dimension sharded over 'model' through the loss —
            # unsharded fp32 logits would dominate peak HBM at 256k vocab
            logits = constrain(logits, ("pod", "data"), None, "model")
            labels = batch["tokens"][:, 1:]
            lg = logits[:, :-1].astype(jnp.float32)
            lg = constrain(lg, ("pod", "data"), None, "model")
            logz = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
            return jnp.mean(logz - gold)

    # ---------------- prefill ----------------

    def prefill(self, params: Params, cache: dict, tokens: jax.Array,
                media: jax.Array | None = None,
                mask: jax.Array | None = None) -> tuple[jax.Array, dict]:
        """Fill the decode cache from a (B, T) prompt; returns last-position
        logits and the cache positioned at T.  ``mask`` (B, T), false at
        padding: the hybrids' mamba layers leave their state unchanged
        there (attention attends to it, as in the other families)."""
        cfg = self.cfg
        T = tokens.shape[1]
        batch = {"tokens": tokens}
        if media is not None:
            batch["media"] = media
        x = self.embed_inputs(params, batch)
        B = x.shape[0]
        positions = jnp.arange(x.shape[1])[None, :].repeat(B, 0)
        flags = self._layer_is_global()

        if cfg.family in ("dense", "moe", "audio"):
            if "moe_blocks" in params:
                x, cache = self._moe_grouped_pass(params, cache, x,
                                                  positions, 0)
            else:
                def layer(x, inp):
                    blk, is_global, kc, vc = inp
                    x, (nk, nv) = self._decoder_layer(
                        blk, x, positions, is_global, kv_cache=(kc, vc),
                        cache_len=0)
                    return x, (nk, nv)

                layer = layers.maybe_remat(layer, cfg.remat_policy)
                x, (nk, nv) = self._scan(
                    layer, x,
                    (params["blocks"], flags, cache["k"], cache["v"]))
                cache = {**cache, "k": nk, "v": nv}
        elif cfg.family == "vlm":
            # fill media K/V once, then run the decode-group path over T
            cross = params["cross_blocks"]
            mtok = jnp.einsum("bmd,dk->bmk", media.astype(x.dtype),
                              params["media_proj"])
            mk = jnp.einsum("bmd,gdhk->gbmhk", mtok, cross["attn"]["wk"])
            mv = jnp.einsum("bmd,gdhk->gbmhk", mtok, cross["attn"]["wv"])
            cache = {**cache, "media_k": mk.astype(cache["media_k"].dtype),
                     "media_v": mv.astype(cache["media_v"].dtype)}
            x, cache = self._decode_vlm(params, cache, x, positions, media)
        elif cfg.family == "ssm":
            def layer(x, inp):
                blk, conv, h = inp
                x, (nc, nh) = self._ssm_layer(blk, x, state=(conv, h))
                return x, (nc, nh)

            layer = layers.maybe_remat(layer, cfg.remat_policy)
            x, (nc, nh) = self._scan(
                layer, x, (params["blocks"], cache["conv"], cache["h"]))
            cache = {**cache, "conv": nc, "h": nh}
        elif cfg.family == "hybrid":
            x, cache = self._hybrid_scan(params, cache, x, positions, mask)

        x = layers.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
        logits = self._unembed(params, x)
        cache = {**cache, "pos": jnp.asarray(
            T + (cfg.n_media_tokens if cfg.family == "audio" else 0),
            jnp.int32)}
        return logits, cache

    # ---------------- decode ----------------

    def init_cache(self, batch_size: int, max_len: int) -> dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        L, K, Dh = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
        cache: dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
        if cfg.family in ("dense", "moe", "audio", "vlm"):
            cache["k"] = jnp.zeros((L, batch_size, max_len, K, Dh), dtype)
            cache["v"] = jnp.zeros((L, batch_size, max_len, K, Dh), dtype)
        if cfg.family == "vlm":
            n_cross = cfg.n_layers // cfg.cross_attn_every
            cache["media_k"] = jnp.zeros(
                (n_cross, batch_size, cfg.n_media_tokens, K, Dh), dtype)
            cache["media_v"] = jnp.zeros_like(cache["media_k"])
        if cfg.family in ("ssm", "hybrid"):
            # conv window and SSM state for the mamba layers only, K/V for
            # the attention layers only
            kinds = ([k for k, _ in self._hybrid_schedule()]
                     if cfg.family == "hybrid" else ["mamba"] * L)
            n_ssm = kinds.count("mamba")
            n_attn = len(kinds) - n_ssm
            di, n = cfg.d_inner, cfg.ssm_state
            cache["conv"] = jnp.zeros(
                (n_ssm, batch_size, cfg.ssm_conv - 1, ssm.conv_width(cfg)),
                dtype)
            if cfg.mamba_version == 1:
                cache["h"] = jnp.zeros((n_ssm, batch_size, di, n),
                                       jnp.float32)
            else:
                H = di // cfg.ssm_head_dim
                cache["h"] = jnp.zeros(
                    (n_ssm, batch_size, H, cfg.ssm_head_dim, n), jnp.float32)
            if n_attn:
                cache["k"] = jnp.zeros(
                    (n_attn, batch_size, max_len, K, Dh), dtype)
                cache["v"] = jnp.zeros_like(cache["k"])
        return cache

    def decode_step(self, params: Params, cache: dict, tokens: jax.Array,
                    media: jax.Array | None = None
                    ) -> tuple[jax.Array, dict]:
        """One serve step: tokens (B, 1) -> logits (B, 1, V), updated cache."""
        cfg = self.cfg
        with jax.named_scope(layers.EMBED):
            x = self._scale_embedding(params["embed"][tokens])
        pos = cache["pos"]
        B = tokens.shape[0]
        positions = jnp.full((B, 1), pos, jnp.int32)
        flags = self._layer_is_global()

        if cfg.family in ("dense", "moe", "audio"):
            if "moe_blocks" in params:
                x, cache = self._moe_grouped_pass(params, cache, x,
                                                  positions, pos)
            else:
                # unrolled: each layer writes its token into the stacked
                # cache and reads its own slice at an index the compiler
                # sees as a constant, both in place, where a scan would
                # slice every layer's cache out as xs and stack the new
                # ones as ys.  One jitted layer, traced once and called
                # per layer, keeps tracing and lowering at one layer's cost
                layer = jax.jit(self._decoder_layer)
                kv = (cache["k"], cache["v"])
                for i in range(cfg.n_layers):
                    x, kv = layer(_take(params["blocks"], i), x, positions,
                                  flags[i], kv_cache=kv, cache_len=pos,
                                  layer=jnp.int32(i))
                cache = {**cache, "k": kv[0], "v": kv[1]}
        elif cfg.family == "vlm":
            x, cache = self._decode_vlm(params, cache, x, positions, media)
        elif cfg.family == "ssm":
            def layer(x, inp):
                blk, conv, h = inp
                x, (nc, nh) = self._ssm_layer(blk, x, state=(conv, h))
                return x, (nc, nh)

            x, (nc, nh) = self._scan(
                layer, x, (params["blocks"], cache["conv"], cache["h"]))
            cache = {**cache, "conv": nc, "h": nh}
        elif cfg.family == "hybrid":
            x, cache = self._hybrid_decode(params, cache, x, positions)

        x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._unembed(params, x)
        cache = {**cache, "pos": pos + 1}
        return logits, cache

    def _moe_grouped_pass(self, params, cache, x, positions, pos):
        """Cached pass for moe_every>1 (llama4): cache rows are laid out as
        [dense layers in scan order, then moe layers]."""
        cfg = self.cfg
        k = cfg.moe_every - 1
        n_groups = cfg.n_layers // cfg.moe_every
        n_dense = n_groups * k
        dense = jax.tree.map(
            lambda a: a.reshape(n_groups, k, *a.shape[1:]), params["blocks"])
        kd = cache["k"][:n_dense].reshape(n_groups, k, *cache["k"].shape[1:])
        vd = cache["v"][:n_dense].reshape(n_groups, k, *cache["v"].shape[1:])
        km, vm = cache["k"][n_dense:], cache["v"][n_dense:]

        def group(x, inp):
            dgrp, mblk, kc, vc, kmc, vmc = inp

            def inner(x, st):
                blk, kcc, vcc = st
                x, (nk, nv) = self._decoder_layer(
                    blk, x, positions, True, kv_cache=(kcc, vcc),
                    cache_len=pos)
                return x, (nk, nv)

            x, (nkd, nvd) = self._scan(inner, x, (dgrp, kc, vc))
            x, (nkm, nvm) = self._decoder_layer(
                mblk, x, positions, True, kv_cache=(kmc, vmc), cache_len=pos)
            return x, (nkd, nvd, nkm, nvm)

        x, (nkd, nvd, nkm, nvm) = self._scan(
            group, x, (dense, params["moe_blocks"], kd, vd, km, vm))
        cache = {**cache,
                 "k": jnp.concatenate(
                     [nkd.reshape(n_dense, *nkd.shape[2:]), nkm]),
                 "v": jnp.concatenate(
                     [nvd.reshape(n_dense, *nvd.shape[2:]), nvm])}
        return x, cache

    def _decode_vlm(self, params, cache, x, positions, media):
        cfg = self.cfg
        k = cfg.cross_attn_every
        n_groups = cfg.n_layers // k
        pos = cache["pos"]
        blocks = jax.tree.map(
            lambda a: a.reshape(n_groups, k, *a.shape[1:]), params["blocks"])
        flags = self._layer_is_global().reshape(n_groups, k)
        kr = cache["k"].reshape(n_groups, k, *cache["k"].shape[1:])
        vr = cache["v"].reshape(n_groups, k, *cache["v"].shape[1:])

        def group(x, inp):
            grp, cross, fl, kc, vc, mk, mv = inp

            def self_layer(x, inner):
                blk, g, kcc, vcc = inner
                x, (nk, nv) = self._decoder_layer(
                    blk, x, positions, g, kv_cache=(kcc, vcc), cache_len=pos)
                return x, (nk, nv)

            x, (nk, nv) = self._scan(self_layer, x, (grp, fl, kc, vc))
            h = layers.rms_norm(x, cross["ln"], cfg.norm_eps)
            # cross-attn against the cached media K/V (computed at prefill)
            with jax.named_scope(layers.ATTENTION):
                spec = self._attn_spec()
                q = jnp.einsum("btd,dhk->bthk", h, cross["attn"]["wq"])
                out = layers.attention(q, mk, mv, spec,
                                       q_offset=mk.shape[1], is_global=True)
                a = jnp.einsum("bthk,hkd->btd", out, cross["attn"]["wo"])
            x = x + jnp.tanh(cross["gate"]).astype(x.dtype) * a
            return x, (nk, nv)

        x, (nk, nv) = self._scan(
            group, x, (blocks, params["cross_blocks"], flags, kr, vr,
                       cache["media_k"], cache["media_v"]))
        cache = {**cache,
                 "k": nk.reshape(cfg.n_layers, *nk.shape[2:]),
                 "v": nv.reshape(cfg.n_layers, *nv.shape[2:])}
        return x, cache


def build(cfg: ModelConfig) -> Model:
    return Model(cfg)
