"""Serving cells of a layer-pattern hybrid (granite-4.0-h: Mamba-2 layers
and attention layers): a closed loop of static batches through
``Engine.generate``, as ``drive_serve.py`` runs the dense cells, with the
hybrid's own weights (``hybrid_weights.py``), reference
(``hybrid_reference.py``) and counts of work (``hybrid_shapes.py``).

Set-up draws the weights, builds the engine through the program's normal
path (``models.model.build``, ``serve.engine.Engine``) and serves one
batch of every prompt length the mix uses, one new token each, which
compiles (or loads from the cache) every program the window runs.  The
window serves whole batches until ``--seconds`` have passed.

``correct`` compares the served tokens themselves, once the window has
closed and the program's weights and cache are freed: requests drawn from
the seed (the longest among them) run through the float32 reference over
prompt and served tokens, and each served token's reference logit is
compared with the reference's best at that position
(``drive_serve.normalized_gap``).

A traced run also reads the layer scopes out of its trace
(``layer_trace.py``) before it returns: each kind's device time per call
of ``decode_step`` and ``prefill``, the mamba mixers' (``ssm``) per 1000
prompt tokens of ``prefill``, and the least time of the bytes the mixers
must move per decode step (``hybrid_shapes.ssm_decode_bytes``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import time

import numpy as np

from benchmarks.chip import (compiles, hybrid_reference, hybrid_shapes,
                             hybrid_weights, layer_trace, shapes, trace,
                             traffic, weights)
from benchmarks.chip.drive_serve import normalized_gap, sample
from benchmarks.chip.harness import Outcome
from benchmarks.chip.hybrid_shapes import HybridSizes


def model_config(cell, s: HybridSizes):
    """The program's registry entry with the configuration file's sizes."""
    from repro.configs import registry

    c = cell.config
    if s.d_inner != c["mamba_expand"] * s.d:
        raise ValueError(f"{c['name']}: mamba_n_heads x mamba_d_head = "
                         f"{s.d_inner}, not mamba_expand x hidden_size")
    return dataclasses.replace(
        registry.get(c["program"]), name=c["name"], n_layers=s.layers,
        d_model=s.d, n_heads=s.heads, n_kv_heads=s.kv_heads,
        head_dim=s.head_dim, d_ff=s.ff, vocab_size=s.vocab,
        tie_embeddings=s.tied, norm_eps=s.norm_eps,
        layer_types=s.layer_types, ssm_state=s.state, ssm_conv=s.conv,
        ssm_expand=c["mamba_expand"], ssm_head_dim=s.mamba_head_dim,
        ssm_groups=s.groups, ssm_chunk=s.chunk,
        embedding_multiplier=s.embedding_multiplier,
        attention_multiplier=s.attention_multiplier,
        residual_multiplier=s.residual_multiplier,
        logits_scaling=s.logits_scaling, dtype="bfloat16")


def engine(cell, s: HybridSizes, seed: int):
    import jax

    from repro.models import model as model_lib
    from repro.serve.engine import Engine, ServeConfig

    mix = cell.mix
    model = model_lib.build(model_config(cell, s))
    draw = functools.partial(hybrid_weights.serving_weights, s=s,
                             embed_std=cell.config["init"]["embed_std"])
    return Engine(model, jax.jit(draw)(weights.root_key(seed)),
                  ServeConfig(max_batch=mix["batch"], max_len=mix["max_len"],
                              temperature=0.0, eos_token=mix["eos_token"]))


def _steps(batches: list):
    """(prompt length, step j, live requests) of every decode step."""
    for prompt_len, served in batches:
        for j in range(1, max(served)):
            yield prompt_len, j, [n for n in served if n > j]


def least_seconds(s: HybridSizes, batches: list, peaks: dict) -> float:
    """Least chip time for the work the window's requests needed."""
    total = sum(shapes.least_seconds(
        hybrid_shapes.prefill_work(s, [p] * len(served)), peaks)
        for p, served in batches)
    for p, j, live in _steps(batches):
        total += shapes.least_seconds(
            hybrid_shapes.decode_work(s, [p + j - 1] * len(live)), peaks)
    return total


def ssm_least_ms(s: HybridSizes, batches: list, peaks: dict
                 ) -> float | None:
    """Mean over the window's decode steps of the least time of the bytes
    the mamba mixers must move, in ms."""
    steps = [hybrid_shapes.ssm_decode_bytes(s, len(live))
             for _, _, live in _steps(batches)]
    if not steps:
        return None
    return float(np.mean(steps)) / peaks["hbm_bytes_per_s"] * 1e3


def layer_readings(trace_dir: str, prefill_tokens: int) -> dict:
    """The ``ssm`` scope's device ms per decode_step and per 1000 prompt
    tokens of prefill, and each layer kind's device ms per call of each
    program (``<program>_ms.<kind>``), where the trace has them."""
    layers = layer_trace.reduce(trace.find_xplane(trace_dir))
    out = {f"{program}_ms.{kind}": layers.per_call_ms(program, kind)
           for program in ("decode_step", "prefill")
           for kind in layers.kinds.get(program, {})}
    decode = layers.per_call_ms("decode_step", "ssm")
    if decode:
        out["ssm_device_ms.decode"] = decode
    prefill = layers.kinds.get("prefill", {}).get("ssm")
    if prefill and prefill_tokens:
        out["ssm_device_ms_per_ktok.prefill"] = (
            prefill * 1e3 / (prefill_tokens / 1000))
    return out


def run(cell, seed: int, seconds: float, trace_dir: str | None, t0: float,
        peaks: dict, controls: bool = False) -> Outcome:
    import jax

    # a traced run reads layer scopes from the compiled programs' metadata,
    # which the cache key leaves out unless told: a program cached by code
    # with other scopes is then never loaded
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    mix, s = cell.mix, HybridSizes.from_config(cell.config)
    eng = engine(cell, s, seed)
    for n in sorted(set(traffic.cycle_lengths(mix))):
        eng.generate([[2] * n] * mix["batch"], max_new=1)
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
                    outs = eng.generate(prompts, max_new=mix["new_tokens"])
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
    gauges = eng.metrics.snapshot()["gauges"]
    del eng
    gc.collect()

    served = sum(sum(n) for _, n in window_batches)
    failed = sum(len(o) - p < mix["new_tokens"] and o[-1] != mix["eos_token"]
                 for p, o, _ in records)
    # greedy decoding that repeats its input token would say the input
    # token's own logit dominates (see the configuration's init)
    repeats = sum(sum(a == b for a, b in zip(o[p - 1:-1], o[p:]))
                  for p, o, _ in records)
    latencies = [r[2] for r in records]
    prefill_tokens = sum(p * len(n) for p, n in window_batches)
    counters = {
        "least_s": least_seconds(s, window_batches, peaks),
        "prefill_tokens": prefill_tokens, "served_tokens": served,
        "requests": len(records), "repeat_share": repeats / max(served, 1),
        "median_batch_s": float(np.median(latencies)),
        "slowest_batch_s": max(latencies),
        **{name: g["last"] for name, g in gauges.items()
           if name.startswith("engine.cache_bytes.")}}
    if trace_dir:
        counters.update(layer_readings(trace_dir, prefill_tokens))
        least = ssm_least_ms(s, window_batches, peaks)
        if least is not None:
            counters["ssm_least_ms.decode"] = least

    t = time.perf_counter()
    checked = sample(records, mix["check_requests"], seed)
    ref = hybrid_reference.ServeReference(seed, s,
                                          cell.config["init"]["embed_std"])
    seqs = [np.asarray(o, np.int32) for _, o, _ in checked]
    firsts = [p for p, _, _ in checked]
    ref_logits = ref.logits(seqs, firsts)
    gap = max(normalized_gap(lg, q[f:]) for lg, q, f
              in zip(ref_logits, seqs, firsts))
    readings = {}
    if controls:
        low = ref.logits(seqs, firsts, cast="fp8")
        readings["control"] = {"logit_gap": max(
            normalized_gap(lg, np.asarray(c.argmax(-1)))
            for lg, c in zip(ref_logits, low))}
    counters.update(checked_tokens=sum(len(q) - f
                                       for q, f in zip(seqs, firsts)),
                    check_s=time.perf_counter() - t)
    return Outcome(
        e2e={"setup_s": setup_s, "decode_tokens_per_s": served / window},
        counters=counters, window_s=window, attempted=len(records),
        failed=failed, numbers={"logit_gap": gap}, readings=readings,
        memory_peak_bytes=int(memory), compiles_in_window=log.count,
        chips=cell.chips)
