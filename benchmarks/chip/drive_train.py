"""Training cells: ``Trainer.run`` over the program's donated, jitted step.

Set-up builds one training loop (state, step and data pipeline) and drives
it through its first ``check_steps`` steps with the same ``Trainer.run``
and data feed that the window uses; the first step compiles.  Between
``run`` calls the benchmark reads, on the device, what the check needs
before the next step donates the state: after step 1, each leaf's norm of
the clipped gradient that the optimizer got (its first moment over
1 - b1); after the last check step, each leaf's norm of the weights'
change since the seed's draw.  That reading is not counted in ``setup_s``.
The window then continues the same loop, on rows not seen before, until
``--seconds`` have passed.

``correct`` holds those readings and the check steps' losses against the
float32 reference run from the same weights over the same rows.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import statistics
import time

import numpy as np

from benchmarks.chip import compiles, program, reference, shapes, weights
from benchmarks.chip.harness import Outcome

# a leaf whose reference gradient is under this share of the median leaf's
# moves by round-off alone (see PERF.md): it is left out of the comparison
NEGLIGIBLE = 1e-3


def worst_leaf(program_norms: dict, ref_norms: dict, keep: set) -> float:
    """Largest gap between the program's and the reference's norm of a
    leaf, over that leaf's reference norm or the median leaf's, whichever
    is larger."""
    med = statistics.median(ref_norms[k] for k in keep)
    return max(abs(program_norms[k] - ref_norms[k]) / max(ref_norms[k], med)
               for k in keep)


def compare(prog: dict, ref: dict) -> dict[str, float]:
    grads = ref["grad_norms"]
    med = statistics.median(grads.values())
    keep = {k for k, g in grads.items() if g >= NEGLIGIBLE * med}
    return {
        "loss_gap": max(abs(p - r) / abs(r) for p, r
                        in zip(prog["losses"], ref["losses"])),
        "grad_gap": worst_leaf(prog["grad_norms"], grads, keep),
        "update_gap": worst_leaf(prog["change_norms"], ref["change_norms"],
                                 keep),
    }


def _leaf_norms_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        return {jax.tree_util.keystr(p): jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)))) for p, x in flat}
    return norms


def run(cell, seed: int, seconds: float, trace_dir: str | None, t0: float,
        peaks: dict, controls: bool = False) -> Outcome:
    import jax
    import jax.numpy as jnp
    from repro.sharding.context import use_mesh

    mix, s = cell.mix, cell.sizes
    n_check, b1 = mix["check_steps"], mix["optimizer"]["b1"]
    window = {"open": False, "w0": 0.0, "length": 0.0, "calls": 0}
    holder = {}

    def wrap(step):
        def call(state, batch):
            with jax.profiler.TraceAnnotation("step_call"):
                out = step(state, batch)
            if window["open"]:
                window["calls"] += 1
                if time.perf_counter() - window["w0"] >= window["length"]:
                    loop = holder["loop"]
                    loop.cfg.total_steps = loop.start_step + window["calls"]
            return out
        return call

    loop, mesh = program.trainer(cell, seed, wrap)
    holder["loop"] = loop
    norms = _leaf_norms_fn()
    embed_std = cell.config["init"]["embed_std"]
    draw = jax.jit(functools.partial(weights.serving_weights, s=s,
                                     embed_std=embed_std))
    reading_s = 0.0
    with use_mesh(mesh):
        loop.cfg.total_steps = 1
        loop.run()
        t = time.perf_counter()
        grad_p = {k: float(v) / (1 - b1) for k, v in
                  norms(loop.state["opt"]["m"]).items()}
        reading_s += time.perf_counter() - t
        loop.start_step, loop.cfg.total_steps = 1, n_check
        loop.run()
        t = time.perf_counter()
        start = draw(weights.root_key(seed))
        change_p = {k: float(v) for k, v in norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            loop.state["params"], start)).items()}
        del start
        reading_s += time.perf_counter() - t
        losses_p = [m["loss"] for m in loop.metrics_log[:n_check]]
        setup_s = time.perf_counter() - t0 - reading_s

        tracing = (jax.profiler.trace(trace_dir) if trace_dir
                   else contextlib.nullcontext())
        loop.start_step, loop.cfg.total_steps = n_check, 2 ** 62
        with compiles.CompileLog() as log, tracing:
            with jax.profiler.TraceAnnotation("window"):
                window.update(open=True, w0=time.perf_counter(),
                              length=seconds)
                loop.run()
                w_s = time.perf_counter() - window["w0"]
    steps = loop.cfg.total_steps - n_check
    memory = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()[:cell.chips])
    rows = [loop.corpus.batch_at(k)["tokens"] for k in range(n_check)]
    step_s = [m["sec_per_step"] for m in loop.metrics_log[n_check:]]
    holder.clear()
    del loop
    gc.collect()

    prog = {"losses": losses_p, "grad_norms": grad_p,
            "change_norms": change_p}
    t = time.perf_counter()
    ref = reference.train_reference(seed, s, embed_std, rows,
                                    mix["optimizer"])
    numbers = compare(prog, ref)
    check_s = time.perf_counter() - t
    readings = {}
    if controls:
        low = reference.train_reference(seed, s, embed_std, rows,
                                        mix["optimizer"], cast="fp8")
        readings["control"] = compare(low, ref)
        half = reference.train_reference(seed, s, embed_std, rows,
                                          mix["optimizer"],
                                          rows=mix["batch"] // 2)
        readings["half_batch"] = compare(half, ref)
    tokens = steps * mix["batch"] * mix["seq"]
    least = steps * shapes.least_seconds(
        shapes.train_work(s, mix["batch"], mix["seq"]), peaks)
    return Outcome(
        e2e={"setup_s": setup_s, "train_tokens_per_s": tokens / w_s},
        counters={"least_s": least, "steps": steps, "tokens": tokens,
                  "median_step_s": statistics.median(step_s),
                  "slowest_step_s": max(step_s), "check_s": check_s},
        window_s=w_s, attempted=steps, failed=0,
        numbers=numbers, readings=readings,
        memory_peak_bytes=int(memory), compiles_in_window=log.count,
        chips=cell.chips)
