"""Batched serving engine: continuous-batching prefill/decode over the model.

A deliberately compact production shape: static max-batch slots, prompt
prefill into per-slot cache regions, greedy/temperature sampling, and slot
recycling when sequences finish — the serving counterpart of the trainer.

What an operator can see: run ``generate`` under ``jax.profiler.trace`` and
the host thread that calls it shows the spans ``engine.generate`` (the whole
call), ``engine.admit`` (padding, token array, cache, media),
``engine.prefill`` (prefill and the first sample) and, per decode
iteration, ``engine.read_tokens`` (the batch's token read, EOS
bookkeeping and stop test) and ``engine.decode`` (key, ``decode_step`` and
sample).  Each carries ``batch=<n>``, the engine's count of ``generate``
calls; the per-iteration ones also ``step=<k>``.  The counters in
``Engine.metrics.snapshot()["counters"]`` are ``engine.host_reads``
(device-to-host reads), ``engine.decode_steps`` (``decode_step`` calls) and
``engine.decode_steps_kept`` (calls whose sampled token some request
appended), and per decode step ``engine.decode_kv_positions_held`` (the
cache's ``max_len`` positions) and ``engine.decode_kv_positions_read``
(what one layer's attention reads of them: on a TPU, where the decode
kernel takes the shapes, the live positions rounded up to its block, else
all of them).  The gauges ``engine.cache_bytes.kv`` and
``engine.cache_bytes.state`` record, as each batch's cache is built, the
bytes it holds for attention keys and values and for recurrent state (a
mamba layer's conv window and SSM state).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import Model
from repro.obs.metrics import MetricsRegistry


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    max_len: int = 256
    temperature: float = 0.0     # 0 -> greedy
    eos_token: int = 1
    seed: int = 0


class Engine:
    def __init__(self, model: Model, params, cfg: ServeConfig):
        self.model = model
        self.params = params
        self.cfg = cfg
        # the engine's two programs: (params, cache, tokens, media) ->
        # (logits, cache).  Each takes the cache's buffers over (donated),
        # so the new cache is written where the old one was: a caller
        # passes a cache once and goes on with the one returned
        self.decode = jax.jit(model.decode_step, donate_argnums=(1,))
        self.prefill = jax.jit(model.prefill, donate_argnums=(1,))
        self.metrics = MetricsRegistry()
        self._batches = 0

    def generate(self, prompts: list[list[int]], max_new: int = 32,
                 media: np.ndarray | None = None) -> list[list[int]]:
        """Generate continuations for a batch of prompts (one static batch).

        Prompts are left-padded to a common length so a single batched
        prefill fills every slot's cache; decode then proceeds lockstep with
        per-slot EOS masking.
        """
        cfg = self.cfg
        B = len(prompts)
        assert B <= cfg.max_batch
        self._batches += 1
        n = self._batches
        reads = self.metrics.counter("engine.host_reads")
        steps = self.metrics.counter("engine.decode_steps")
        kept = self.metrics.counter("engine.decode_steps_kept")
        held = self.metrics.counter("engine.decode_kv_positions_held")
        read = self.metrics.counter("engine.decode_kv_positions_read")
        blk = self._decode_block(B)
        with jax.profiler.TraceAnnotation("engine.generate", batch=n):
            with jax.profiler.TraceAnnotation("engine.admit", batch=n):
                plen = max(len(p) for p in prompts)
                toks = np.zeros((B, plen), np.int32)
                for i, p in enumerate(prompts):
                    toks[i, plen - len(p):] = p          # left-pad
                # where there is padding, prefill is told where it lies
                lens = np.array([len(p) for p in prompts])
                pad = ((jnp.asarray(np.arange(plen) >= plen - lens[:, None]),)
                       if lens.min() < plen else ())
                cache = self.model.init_cache(B, cfg.max_len)
                self._record_cache(cache)
                m = (jnp.asarray(media) if media is not None else
                     (jnp.zeros((B, self.model.cfg.n_media_tokens,
                                 self.model.cfg.media_embed_dim),
                                jnp.float32)
                      if self.model.cfg.n_media_tokens else None))
            with jax.profiler.TraceAnnotation("engine.prefill", batch=n):
                logits, cache = self.prefill(self.params, cache,
                                             jnp.asarray(toks), m, *pad)
                key = jax.random.key(cfg.seed)
                cur = self._sample(logits, key)
            out = [list(p) for p in prompts]
            done = np.zeros(B, bool)
            # the cache's position, read once: each decode step adds one
            pos = int(cache["pos"])
            reads.inc()
            for step in range(max_new):
                with jax.profiler.TraceAnnotation("engine.read_tokens",
                                                  batch=n, step=step):
                    # the whole batch's tokens in one device-to-host read
                    new = np.asarray(cur)[:, 0]
                    reads.inc()
                    appended = 0
                    for i in np.flatnonzero(~done):
                        t = int(new[i])
                        out[i].append(t)
                        done[i] |= t == cfg.eos_token
                        appended += 1
                    stop = done.all() or pos >= cfg.max_len - 1
                if step and appended:
                    kept.inc()
                if stop:
                    break
                with jax.profiler.TraceAnnotation("engine.decode",
                                                  batch=n, step=step):
                    key = jax.random.fold_in(key, step)
                    logits, cache = self.decode(self.params, cache, cur, m)
                    cur = self._sample(logits, key)
                    pos += 1
                steps.inc()
                if blk is not None:
                    # the step's token sits at pos - 1: pos positions live
                    held.inc(cfg.max_len)
                    read.inc(-(-pos // blk) * blk if blk else cfg.max_len)
        return out

    def _decode_block(self, batch: int) -> int | None:
        """The block in which this platform's decode step reads the K/V
        cache, 0 where it reads all of it, None where it holds none."""
        blk = self.model.decode_kv_block(batch, self.cfg.max_len)
        return 0 if blk and jax.default_backend() != "tpu" else blk

    def _record_cache(self, cache: dict) -> None:
        now = time.perf_counter_ns()
        for kind, names in (("kv", ("k", "v", "media_k", "media_v")),
                            ("state", ("conv", "h"))):
            self.metrics.gauge(f"engine.cache_bytes.{kind}").record(
                now, sum(cache[n].nbytes for n in names if n in cache))

    def _sample(self, logits: jax.Array, key) -> jax.Array:
        lg = logits[:, -1, :]
        if self.cfg.temperature <= 0:
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]
        return jax.random.categorical(
            key, lg / self.cfg.temperature, axis=-1
        ).astype(jnp.int32)[:, None]
