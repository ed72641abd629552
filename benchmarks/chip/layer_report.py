"""Per-layer readings of one cell on the chip, from the program's own
spans, counters and layer scopes (``layer_trace.py``).  A tool to find the
layer a change should target; it is not part of a measured run.

    python3 benchmarks/chip/layer_report.py --workload <cell> --seed <n> \
        [--seconds <s>] [--steps <k>]

It builds the cell's serving engine or training loop as a run does
(``program.py``), warms up every shape, then traces a window: whole
batches until ``--seconds`` have passed (serving) or ``--steps`` steps of
``Trainer.run`` (training).  The last line of standard output is one JSON
object: ``readings`` (the per-step numbers below, where the window has
what they read), ``idle_s`` (device-idle seconds under each span),
``kinds_ms`` (device ms of each layer kind per call of each program),
``top_ops`` (the model programs' longest operations, ms per call, with
their kinds), ``counters`` (the engine's counters over the window) and
the clock offset with its spread.  Compiled programs are cached with their metadata in the
key, so that a program cached by code with other scopes is never loaded.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TRACE_DIR = ROOT / ".bench_traces" / "layers"
# the engine's and the train loop's programs, as the trace names them
MODEL_PROGRAMS = ("decode_step", "prefill", "step")


def serve_window(cell, seed: int, seconds: float, trace_dir: str) -> dict:
    """Trace whole batches for ``seconds``; the engine's counters over the
    window and the prompt tokens it prefilled."""
    import jax

    from benchmarks.chip import program, traffic

    mix = cell.mix
    engine = program.engine(cell, seed)
    for n in sorted(set(traffic.cycle_lengths(mix))):
        engine.generate([[2] * n] * mix["batch"], max_new=1)
    batches = traffic.serve_batches(mix, cell.sizes.vocab, seed)
    before = engine.metrics.snapshot()["counters"]
    prompt_tokens = 0
    with jax.profiler.trace(trace_dir):
        with jax.profiler.TraceAnnotation("window"):
            w0 = time.perf_counter()
            while time.perf_counter() - w0 < seconds:
                prompts = next(batches)
                with jax.profiler.TraceAnnotation("generate"):
                    engine.generate(prompts, max_new=mix["new_tokens"])
                prompt_tokens += sum(len(p) for p in prompts)
    after = engine.metrics.snapshot()["counters"]
    return {"prefill_tokens": prompt_tokens,
            **{k: v - before.get(k, 0) for k, v in after.items()}}


def train_window(cell, seed: int, steps: int, trace_dir: str) -> dict:
    """Trace ``steps`` steps of ``Trainer.run`` after one that compiles."""
    import jax

    from benchmarks.chip import program
    from repro.sharding.context import use_mesh

    loop, mesh = program.trainer(cell, seed, lambda step: step)
    with use_mesh(mesh):
        loop.cfg.total_steps = 1
        loop.run()
        loop.start_step, loop.cfg.total_steps = 1, 1 + steps
        with jax.profiler.trace(trace_dir):
            with jax.profiler.TraceAnnotation("window"):
                loop.run()
    return {}


def readings(layers, counters: dict) -> dict:
    """The per-step numbers, named as the per-layer metrics that would read
    them; a number whose program or span is not in the window is left
    out."""
    per_step = layers.per_call_ms
    out = {
        "decode_read_idle_ms": layers.idle_per_call_ms(
            "engine.read_tokens", "decode_step"),
        "decode_dispatch_idle_ms": layers.idle_per_call_ms(
            "engine.decode", "decode_step"),
        "attention_device_ms.decode": per_step("decode_step", "attention"),
        "kv_cache_device_ms.decode": per_step("decode_step", "kv_cache"),
        "attention_device_ms.train": per_step("step", "attention"),
        "mlp_device_ms.train": per_step("step", "mlp"),
        "unembed_device_ms.train": per_step("step", "unembed", "loss"),
    }
    waits = layers.spans.get("trainer.data_wait")
    if waits:
        out["train_data_wait_ms"] = statistics.fmean(waits) * 1e3
    ktok = counters.get("prefill_tokens", 0) / 1000
    if ktok and "prefill" in layers.kinds:
        for kind in ("attention", "mlp"):
            out[f"{kind}_device_ms_per_ktok.prefill"] = (
                layers.kinds["prefill"].get(kind, 0.0) * 1e3 / ktok)
    steps = counters.get("engine.decode_steps", 0)
    if steps:
        out["decode_host_reads_per_step"] = (
            counters["engine.host_reads"] / steps)
        out["decode_step_waste"] = (
            1 - counters["engine.decode_steps_kept"] / steps)
    return {k: v for k, v in out.items() if v is not None}


def top_ops(layers, program: str, n: int = 12) -> list:
    """[op, ms per call, layer kind] of the program's ``n`` longest
    operations."""
    mine = [(sec, op.split(":", 1)[1], kind)
            for op, (sec, kind) in layers.ops.items()
            if op.startswith(program + ":")]
    return [[op, sec / layers.calls[program] * 1e3, kind]
            for sec, op, kind in sorted(mine, reverse=True)[:n]]


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0,
                    help="serving: trace whole batches for this long")
    ap.add_argument("--steps", type=int, default=6,
                    help="training: trace this many steps")
    args = ap.parse_args(argv)

    import jax

    from benchmarks.chip import compiles, harness, layer_trace, run, trace

    cell = harness.load_cell(args.workload)
    compiles.enable_cache()
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    try:
        devices = run.devices_for(cell)
    except run.NoChip as e:
        run.log(str(e))
        return 2
    trace_dir = str(TRACE_DIR / cell.name)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if cell.mix["kind"] == "serve":
        counters = serve_window(cell, args.seed, args.seconds, trace_dir)
    else:
        counters = train_window(cell, args.seed, args.steps, trace_dir)
    path = trace.find_xplane(trace_dir)
    layers = layer_trace.reduce(path)
    outer = trace.reduce(path)
    shutil.rmtree(trace_dir, ignore_errors=True)
    result = {
        "workload": cell.name, "seed": args.seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "readings": readings(layers, counters),
        "window_s": layers.window_s, "idle_s": layers.idle,
        "host_gap_ms": {k: statistics.fmean(g) for k in outer.programs
                        if (g := outer.host_gap_ms(k))},
        "program_ms": {k: s / layers.calls[k] * 1e3
                       for k, s in layers.program_s.items()
                       if layers.calls.get(k)},
        "kinds_ms": {p: {k: v / layers.calls[p] * 1e3 for k, v in
                         sorted(kinds.items(), key=lambda kv: -kv[1])}
                     for p, kinds in layers.kinds.items()
                     if layers.calls.get(p)},
        "top_ops": {p: top_ops(layers, p) for p in MODEL_PROGRAMS
                    if layers.calls.get(p)},
        "calls": layers.calls, "counters": counters,
        "clock_offset_us": layers.offset_us,
        "clock_offset_spread_us": layers.offset_spread_us,
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
