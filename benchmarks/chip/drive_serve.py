"""Serving cells: a closed loop of static batches through ``Engine.generate``.

Set-up draws the weights, builds the engine and serves one batch of every
prompt length the mix uses, one new token each, which compiles (or loads
from the cache) every program the window runs.  The window then serves
whole batches until ``--seconds`` have passed; a request's latency runs
from its batch's submission to ``generate``'s return.

``correct`` compares the served tokens themselves: once the window has
closed and the program's weights and cache are freed, requests drawn from
the seed (the longest among them) are run through the float32 reference
over prompt and served tokens, and each served token's reference logit is
compared with the reference's best at that position.
"""

from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

from benchmarks.chip import compiles, program, reference, shapes, traffic
from benchmarks.chip.harness import Outcome


def normalized_gap(ref, chosen) -> float:
    """Widest gap, over positions, between the reference's best logit and
    its logit of the chosen token, in units of the reference logits'
    standard deviation at that position."""
    import jax.numpy as jnp

    chosen = jnp.asarray(chosen)
    picked = jnp.take_along_axis(ref, chosen[:, None], -1)[:, 0]
    return float(jnp.max((ref.max(-1) - picked) / ref.std(-1)))


def sample(records: list, n: int, seed: int) -> list:
    """The longest request and n-1 others drawn from the seed, each with a
    chance inverse to its length, so that the reference's time buys more
    served tokens while every length can be drawn."""
    longest = max(range(len(records)), key=lambda i: len(records[i][1]))
    rest = [i for i in range(len(records)) if i != longest]
    weight = np.array([1.0 / len(records[i][1]) for i in rest])
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    picked = rng.choice(rest, size=min(n - 1, len(rest)), replace=False,
                        p=weight / weight.sum())
    return [records[i] for i in [longest, *sorted(picked)]]


def least_seconds(s: shapes.Sizes, batches: list, peaks: dict) -> float:
    """Least chip time for the work the window's requests needed."""
    total = 0.0
    for prompt_len, served in batches:
        total += shapes.least_seconds(
            shapes.prefill_work(s, [prompt_len] * len(served)), peaks)
        for j in range(1, max(served)):
            ctx = [prompt_len + j - 1 for n in served if n > j]
            total += shapes.least_seconds(shapes.decode_work(s, ctx), peaks)
    return total


def run(cell, seed: int, seconds: float, trace_dir: str | None, t0: float,
        peaks: dict, controls: bool = False) -> Outcome:
    import jax

    mix, s = cell.mix, cell.sizes
    engine = program.engine(cell, seed)
    for n in sorted(set(traffic.cycle_lengths(mix))):
        engine.generate([[2] * n] * mix["batch"], max_new=1)
    setup_s = time.perf_counter() - t0

    batches = traffic.serve_batches(mix, s.vocab, seed)
    records, window_batches = [], []
    tracing = (jax.profiler.trace(trace_dir) if trace_dir
               else contextlib.nullcontext())
    with compiles.CompileLog() as log, tracing:
        with jax.profiler.TraceAnnotation("window"):
            w0 = time.perf_counter()
            while True:
                prompts = next(batches)
                t = time.perf_counter()
                with jax.profiler.TraceAnnotation("generate"):
                    outs = engine.generate(prompts,
                                           max_new=mix["new_tokens"])
                done = time.perf_counter()
                records += [(len(p), o, done - t)
                            for p, o in zip(prompts, outs)]
                window_batches.append(
                    (len(prompts[0]), [len(o) - len(p)
                                       for p, o in zip(prompts, outs)]))
                if done - w0 >= seconds:
                    break
            window = time.perf_counter() - w0
    memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()[:cell.chips])
    del engine
    gc.collect()

    served = sum(sum(n) for _, n in window_batches)
    # a request that stopped short without choosing the end token failed
    failed = sum(len(o) - p < mix["new_tokens"] and o[-1] != mix["eos_token"]
                 for p, o, _ in records)
    latencies = [r[2] for r in records]
    t = time.perf_counter()
    checked = sample(records, mix["check_requests"], seed)
    ref = reference.ServeReference(seed, s, cell.config["init"]["embed_std"])
    seqs = [np.asarray(o, np.int32) for _, o, _ in checked]
    firsts = [p for p, _, _ in checked]
    ref_logits = ref.logits(seqs, firsts)
    gap = max(normalized_gap(lg, q[f:]) for lg, q, f
              in zip(ref_logits, seqs, firsts))
    check_s = time.perf_counter() - t
    readings = {}
    if controls:
        low = ref.logits(seqs, firsts, cast="fp8")
        readings["control"] = {"logit_gap": max(
            normalized_gap(lg, np.asarray(c.argmax(-1)))
            for lg, c in zip(ref_logits, low))}
    return Outcome(
        e2e={"setup_s": setup_s,
             "decode_tokens_per_s": served / window,
             "request_latency_p95_s": float(np.percentile(latencies, 95))},
        counters={"least_s": least_seconds(s, window_batches, peaks),
                  "prefill_tokens": sum(p * len(n)
                                        for p, n in window_batches),
                  "served_tokens": served, "requests": len(records),
                  "median_batch_s": float(np.median(latencies)),
                  "slowest_batch_s": max(latencies),
                  "checked_tokens": sum(len(q) - f
                                        for q, f in zip(seqs, firsts)),
                  "check_s": check_s},
        window_s=window, attempted=len(records), failed=failed,
        numbers={"logit_gap": gap},
        readings=readings, memory_peak_bytes=int(memory),
        compiles_in_window=log.count, chips=cell.chips)
