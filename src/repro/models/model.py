"""Config-driven model assembly for every registry architecture.

``build(cfg)`` returns a ``Model`` with:

* ``init(key)``                      -> params pytree (stacked layer weights)
* ``forward(params, batch)``         -> logits (training path)
* ``train_loss(params, batch)``      -> scalar LM loss
* ``init_cache(B, max_len)``         -> decode cache pytree (KV / SSM states)
* ``prefill(params, cache, tokens)`` -> (last logits, cache)
* ``decode_step(params, cache, tok)``-> (logits, cache)  [one-token serve step]

Every family's layer stack is one schedule (``Model._schedule``): its
layers in order, each a step of a kind (attention with its MLP or MoE, a
mamba mixer, a cross-attention block), with the weight stack and index it
takes its weights from and its slot in its kind's stacked cache.  ``init``
and ``init_cache`` size the stacks from it, and two walks run it:

* ``decode_step`` is unrolled: each step is one call of a jitted function
  per kind, which reads and writes its own slot of the stacked cache at an
  index the compiler sees as a constant, in place.
* Prefill and training scan the schedule, which keeps those programs and
  their compile times compact.  A stack of one kind scans its weights and
  cache slots as ``xs``; a stack of several kinds carries the cache stacks
  and runs each step's kind through a ``lax.switch``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers, moe, ssm
from repro.models.layers import AttnSpec, Params

# each kind's stacked cache, one slot per step of that kind
_CACHE_SLOTS = {"attention": ("k", "v"), "mamba": ("conv", "h"),
               "cross": ("media_k", "media_v")}

# which of ``init``'s keys draws each weight stack
_INIT_KEYS = {"blocks": 2, "attn_blocks": 3, "shared_attn": 3,
              "cross_blocks": 3, "moe_blocks": 5}


class Step(NamedTuple):
    """One layer of the schedule."""
    kind: str          # "attention" (and its MLP or MoE), "mamba", "cross"
    stack: str         # the params entry that holds its weights
    index: int         # its weights' index in that stack
    slot: int          # its index in its kind's cache stacks
    is_global: bool    # attention: not held to the sliding window


def _stack_init(fn, key, n: int):
    """vmap an init function over n layer keys -> stacked params."""
    return jax.vmap(fn)(jax.random.split(key, n))


def _take(tree, i):
    return jax.tree.map(lambda x: jax.lax.dynamic_index_in_dim(
        x, i, keepdims=False), tree)


@contextlib.contextmanager
def _cache_scope(kind: str):
    """The scopes of a kind's cache writes."""
    if kind == "mamba":
        with jax.named_scope(layers.SSM):
            yield
    else:
        with jax.named_scope(layers.ATTENTION), \
                jax.named_scope(layers.KV_CACHE):
            yield


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ---------------- the layer schedule ----------------

    def _schedule(self) -> tuple[Step, ...]:
        """The stack in order, one step per layer:

        * attention over ``blocks`` (dense, audio, MoE, the VLM's self
          attention); llama4 takes every ``moe_every``-th from
          ``moe_blocks``, and its K/V slots follow the dense layers';
        * mamba over ``blocks`` (falcon-mamba, zamba2), and for
          granite-4.0-h as ``layer_types`` say, its attention layers over
          ``attn_blocks``;
        * zamba2: after every ``attn_every``-th layer, a shared attention
          block (``shared_attn``, its weight sets cycled);
        * the VLM: after every ``cross_attn_every``-th layer, a cross step
          into its slot of the media K/V.
        """
        cfg = self.cfg
        every = cfg.local_global_every
        order = []                              # (kind, stack, is_global)
        for i in range(cfg.n_layers):
            if cfg.layer_types:
                kind = cfg.layer_types[i]
            else:
                kind = ("mamba" if cfg.family in ("ssm", "hybrid")
                        else "attention")
            stack = "blocks"
            if kind == "attention" and cfg.layer_types:
                stack = "attn_blocks"
            elif kind == "attention" and cfg.moe_every > 1 and (
                    i % cfg.moe_every == cfg.moe_every - 1):
                stack = "moe_blocks"
            order.append((kind, stack, not (cfg.sliding_window and every)
                          or i % every == every - 1))
            if cfg.attn_every and i % cfg.attn_every == cfg.attn_every - 1:
                order.append(("attention", "shared_attn", True))
            if cfg.cross_attn_every and (
                    i % cfg.cross_attn_every == cfg.cross_attn_every - 1):
                order.append(("cross", "cross_blocks", True))
        # a kind's cache slots: its stacks one after another, in the order
        # of their first layers
        count, first, taken = {}, {}, {}
        for kind, stack, _ in order:
            count[stack] = count.get(stack, 0) + 1
        for kind, stack, _ in order:
            if stack not in first:
                first[stack] = taken.get(kind, 0)
                taken[kind] = first[stack] + count[stack]
        seen = dict.fromkeys(count, 0)
        steps = []
        for kind, stack, is_global in order:
            n = seen[stack]
            seen[stack] += 1
            index = (n % cfg.n_shared_attn_blocks if stack == "shared_attn"
                     else n)
            steps.append(Step(kind, stack, index, first[stack] + n,
                              is_global))
        return tuple(steps)

    def _weights(self, params: Params, stack: str, i) -> Params:
        """One step's weights: entry ``i`` (may be traced) of ``stack``."""
        blk = _take(params[stack], i)
        if stack == "shared_attn":
            blk = {**blk, "ln1": blk["ln"]}
        return blk

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
        kinds, sizes = {}, {}
        for step in self._schedule():
            kinds[step.stack] = step.kind
            sizes[step.stack] = sizes.get(step.stack, 0) + 1
        if "shared_attn" in sizes:
            sizes["shared_attn"] = cfg.n_shared_attn_blocks
        for stack, n in sizes.items():
            p[stack] = _stack_init(functools.partial(
                self._init_layer, kinds[stack], stack, dtype=dtype),
                keys[_INIT_KEYS[stack]], n)
        if cfg.media_embed_dim:
            p["media_proj"] = layers.dense_init(
                keys[4], cfg.media_embed_dim, (cfg.d_model,), dtype)
        return p

    def _init_layer(self, kind: str, stack: str, key, dtype) -> Params:
        cfg = self.cfg
        if kind == "mamba":
            return self._init_ssm_block(key, dtype)
        if kind == "cross":
            return self._init_cross_block(key, dtype)
        if stack == "shared_attn":
            return self._init_shared_attn(key, dtype)
        moe_mlp = stack == "moe_blocks" or (cfg.n_experts > 0
                                            and cfg.moe_every == 1)
        return self._init_block(key, dtype, moe_mlp)

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

    def _init_block(self, key, dtype, moe_mlp: bool) -> Params:
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        p = {
            "ln1": jnp.zeros((cfg.d_model,), dtype),
            "attn": layers.init_attn_params(ks[0], cfg.d_model,
                                            self._attn_spec(), dtype,
                                            qk_norm=cfg.qk_norm),
            "ln2": jnp.zeros((cfg.d_model,), dtype),
        }
        if moe_mlp:
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

    # ---------------- embedding and unembedding ----------------

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

    def _media_tokens(self, params: Params, media: jax.Array,
                      dtype) -> jax.Array:
        return jnp.einsum("bmd,dk->bmk", media.astype(dtype),
                          params["media_proj"])

    @jax.named_scope(layers.EMBED)
    def embed_inputs(self, params: Params, batch: dict) -> jax.Array:
        """Token embeddings; audio's conditioning frames go in front."""
        cfg = self.cfg
        x = self._scale_embedding(params["embed"][batch["tokens"]])
        if cfg.family == "audio":
            media = self._media_tokens(params, batch["media"], x.dtype)
            x = jnp.concatenate([media, x], axis=1)
        return x

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

    # ---------------- the layers of each kind ----------------

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
            and cfg.position_embedding == "rope")
        x = x + self._residual(a)
        h = layers.rms_norm(x, blk["ln2"], cfg.norm_eps)
        if "moe" in blk:
            x = x + moe.moe_block(blk["moe"], h, cfg)
        else:
            x = x + self._residual(layers.mlp_block(
                blk["mlp"], h, cfg.act, overlap=cfg.overlap == "shared_bus"))
        return x, kv

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

    def _cross_layer(self, blk, x, mk, mv):
        """Gated cross-attention of x into the media's K/V (B, M, K, Dh)."""
        h = layers.rms_norm(x, blk["ln"], self.cfg.norm_eps)
        with jax.named_scope(layers.ATTENTION):
            q = jnp.einsum("btd,dhk->bthk", h, blk["attn"]["wq"])
            out = layers.attention(q, mk, mv, self._attn_spec(),
                                   q_offset=mk.shape[1], is_global=True)
            a = jnp.einsum("bthk,hkd->btd", out, blk["attn"]["wo"])
        return x + jnp.tanh(blk["gate"]).astype(x.dtype) * a

    # one decode step of each kind, over its kind's stacked cache, of which
    # the step's slot is at ``layer``: read there and written back there,
    # in place where the compiler sees a constant index

    def _attn_decode_layer(self, blk, x, kv, layer, positions, pos,
                           is_global):
        return self._decoder_layer(blk, x, positions, is_global,
                                   kv_cache=kv, cache_len=pos, layer=layer)

    def _mamba_decode_layer(self, blk, x, state, layer, *_):
        conv, h = state
        x, (nc, nh) = self._mamba_layer(blk, x, (conv[layer], h[layer]))
        with jax.named_scope(layers.SSM):
            conv = jax.lax.dynamic_update_index_in_dim(
                conv, nc.astype(conv.dtype), layer, 0)
            h = jax.lax.dynamic_update_index_in_dim(h, nh, layer, 0)
        return x, (conv, h)

    def _cross_decode_layer(self, blk, x, media_kv, layer, *_):
        mk, mv = media_kv
        return self._cross_layer(blk, x, mk[layer], mv[layer]), media_kv

    # ---------------- forward (training) and prefill ----------------

    def _walk(self, params, x, positions, cache=None, media=None,
              mask=None):
        """Prefill (with a cache) or training (without) over the schedule,
        as a scan.  Returns (x, cache); without a cache nothing is cached.

        A stack of one kind scans its weights and each layer's cache slots
        as ``xs``.  A stack of several kinds runs each step's kind through
        a ``lax.switch``, with its weights at the step's index.  With a
        cache, a step reads its slots (and, for the other kinds, the slots
        last used) out of the stacked caches, the switch computes on those
        slots alone, and the step writes them back in place; so the
        stacks, carried by the scan, never enter a branch, where the one
        that leaves a stack unchanged would copy it whole."""
        cfg = self.cfg
        steps = self._schedule()
        cached = cache is not None
        # prefill starts at a Python 0: attention reads the prompt's own K/V
        pos = 0 if cached else None
        if any(step.kind == "cross" for step in steps):
            media = self._media_tokens(params, media, x.dtype)

        def attention(blk, x, is_global, slots):
            x, kv = self._decoder_layer(blk, x, positions, is_global,
                                        kv_cache=slots, cache_len=pos)
            return x, kv if cached else None

        def mamba(blk, x, is_global, slots):
            x, state = self._mamba_layer(blk, x, slots, mask)
            return x, state if cached else None

        def cross(blk, x, is_global, slots):
            with jax.named_scope(layers.ATTENTION):
                kv = tuple(jnp.einsum("bmd,dhk->bmhk", media, blk["attn"][w])
                           for w in ("wk", "wv"))
            if cached:
                kv = tuple(a.astype(s.dtype) for a, s in zip(kv, slots))
            return self._cross_layer(blk, x, *kv), kv if cached else None

        run = {"attention": attention, "mamba": mamba, "cross": cross}
        stacks = list(dict.fromkeys((step.kind, step.stack)
                                    for step in steps))
        if len(stacks) == 1:
            # the steps are the stack's entries in order, each its own slot
            (kind, stack), = stacks
            names = _CACHE_SLOTS[kind]

            def layer(x, inp):
                blk, is_global, slots = inp
                return run[kind](blk, x, is_global, slots)

            flags = (jnp.asarray([step.is_global for step in steps])
                     if kind == "attention" else None)
            layer = layers.maybe_remat(layer, cfg.remat_policy)
            x, slots = self._scan(layer, x, (
                params[stack], flags,
                tuple(cache[n] for n in names) if cached else None))
            if cached:
                cache = {**cache, **dict(zip(names, slots))}
            return x, cache

        kinds = list(dict.fromkeys(step.kind for step in steps))
        names = [n for kind in kinds for n in _CACHE_SLOTS[kind]]

        def branch(kind, stack):
            lo = 2 * kinds.index(kind)         # its slots among all slots

            def step(x, slots, i, is_global):
                x, own = run[kind](self._weights(params, stack, i), x,
                                   is_global,
                                   slots[lo:lo + 2] if cached else None)
                return x, (slots[:lo] + own + slots[lo + 2:] if cached
                           else slots)
            return step

        branches = [branch(*s) for s in stacks]

        def layer(carry, step):
            x, caches = carry
            b, i, at, is_global = step          # at: a slot per kind
            slots = (tuple(c[at[j // 2]] for j, c in enumerate(caches))
                     if cached else (None,) * len(names))
            x, slots = jax.lax.switch(b, branches, x, slots, i, is_global)
            if cached:
                new = []
                for j, (c, s) in enumerate(zip(caches, slots)):
                    with _cache_scope(kinds[j // 2]):
                        new.append(jax.lax.dynamic_update_index_in_dim(
                            c, s.astype(c.dtype), at[j // 2], 0))
                caches = tuple(new)
            return (x, caches), None

        # each step's slots: its own, and for the other kinds the last used
        at, last = [], dict.fromkeys(kinds, 0)
        for step in steps:
            last[step.kind] = step.slot
            at.append([last[kind] for kind in kinds])
        sched = (jnp.asarray([stacks.index((s.kind, s.stack)) for s in steps]),
                 jnp.asarray([s.index for s in steps]), jnp.asarray(at),
                 jnp.asarray([s.is_global for s in steps]))
        caches = tuple(cache[n] for n in names) if cached else None
        layer = layers.maybe_remat(layer, cfg.remat_policy)
        (x, caches), _ = self._scan(layer, (x, caches), sched)
        if cached:
            cache = {**cache, **dict(zip(names, caches))}
        return x, cache

    def forward(self, params: Params, batch: dict) -> jax.Array:
        cfg = self.cfg
        x = self.embed_inputs(params, batch)
        B, T, _ = x.shape
        positions = jnp.arange(T)[None, :].repeat(B, 0)
        x, _ = self._walk(params, x, positions, media=batch.get("media"))
        x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        n = batch["tokens"].shape[1]
        if n != T:
            x = x[:, T - n:]                    # strip conditioning frames
        return self._unembed(params, x)

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
        logits and the cache positioned after the prompt (and any
        conditioning frames).  ``mask`` (B, T), false at padding: the mamba
        layers leave their state unchanged there (attention attends to
        it)."""
        cfg = self.cfg
        batch = {"tokens": tokens}
        if media is not None:
            batch["media"] = media
        x = self.embed_inputs(params, batch)
        B, T, _ = x.shape
        positions = jnp.arange(T)[None, :].repeat(B, 0)
        x, cache = self._walk(params, x, positions, cache, media, mask)
        x = layers.rms_norm(x[:, -1:], params["final_norm"], cfg.norm_eps)
        logits = self._unembed(params, x)
        cache = {**cache, "pos": jnp.asarray(T, jnp.int32)}
        return logits, cache

    # ---------------- decode ----------------

    def init_cache(self, batch_size: int, max_len: int) -> dict:
        """Each kind's stacked cache, a slot per step of that kind: K/V for
        attention, media K/V for cross steps, the conv window and SSM state
        for mamba layers."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        K, Dh = cfg.n_kv_heads, cfg.head_dim
        n = {kind: 0 for kind in _CACHE_SLOTS}
        for step in self._schedule():
            n[step.kind] += 1
        cache: dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
        if n["mamba"]:
            di, N = cfg.d_inner, cfg.ssm_state
            cache["conv"] = jnp.zeros(
                (n["mamba"], batch_size, cfg.ssm_conv - 1,
                 ssm.conv_width(cfg)), dtype)
            if cfg.mamba_version == 1:
                state = (di, N)
            else:
                state = (di // cfg.ssm_head_dim, cfg.ssm_head_dim, N)
            cache["h"] = jnp.zeros((n["mamba"], batch_size, *state),
                                   jnp.float32)
        if n["attention"]:
            cache["k"] = jnp.zeros(
                (n["attention"], batch_size, max_len, K, Dh), dtype)
            cache["v"] = jnp.zeros_like(cache["k"])
        if n["cross"]:
            cache["media_k"] = jnp.zeros(
                (n["cross"], batch_size, cfg.n_media_tokens, K, Dh), dtype)
            cache["media_v"] = jnp.zeros_like(cache["media_k"])
        return cache

    def decode_kv_block(self, batch: int, max_len: int) -> int | None:
        """The block in which a decode step's attention reads the K/V
        cache when lowered for a TPU (``layers.decode_block``), 0 where it
        reads all ``max_len`` positions, None for a stack with no
        attention."""
        if all(step.kind != "attention" for step in self._schedule()):
            return None
        return layers.decode_block(self._attn_spec(), batch, max_len,
                                   self.cfg.dtype)

    def decode_step(self, params: Params, cache: dict, tokens: jax.Array,
                    media: jax.Array | None = None
                    ) -> tuple[jax.Array, dict]:
        """One serve step: tokens (B, 1) -> logits (B, 1, V), updated cache.

        Unrolled over the schedule: each step is one call of its kind's
        jitted layer, traced once per kind and weight layout, which keeps
        tracing and lowering at a layer's cost.  A scan would slice every
        layer's cache out as xs and stack the new ones as ys."""
        cfg = self.cfg
        with jax.named_scope(layers.EMBED):
            x = self._scale_embedding(params["embed"][tokens])
        pos = cache["pos"]
        B = tokens.shape[0]
        positions = jnp.full((B, 1), pos, jnp.int32)
        run = {"attention": jax.jit(self._attn_decode_layer),
               "mamba": jax.jit(self._mamba_decode_layer),
               "cross": jax.jit(self._cross_decode_layer)}
        caches = {n: a for n, a in cache.items() if n != "pos"}
        for step in self._schedule():
            names = _CACHE_SLOTS[step.kind]
            x, slots = run[step.kind](
                self._weights(params, step.stack, step.index), x,
                tuple(caches[n] for n in names), jnp.int32(step.slot),
                positions, pos, step.is_global)
            caches.update(zip(names, slots))
        x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = self._unembed(params, x)
        cache = {**cache, **caches, "pos": pos + 1}
        return logits, cache


def build(cfg: ModelConfig) -> Model:
    return Model(cfg)
