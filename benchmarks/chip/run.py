"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

(``python3 -m benchmarks.chip.run`` from the repository root is the same.)
The cell, its configuration, its mix, its metrics and the limits of its
check are found by name from ``BENCHMARK.json`` (see ``harness.py``).

``--trace 0`` measures the cell's end-to-end metrics over a window of
``--seconds``; ``--trace 1`` traces a window of ``--seconds``, at most
``TRACE_SECONDS``, with the profiler and reports the cell's per-layer
metrics.  Either way the run then checks the
timed path's output against the float32 reference.  The last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, with
``--controls 1`` ``controls``, and last ``checks``: each compared number
beside its limit).  The run exits
non-zero and prints no result line on a host without a TPU, with fewer
chips than the cell asks for, or with a device kind that ``peaks.json``
does not list.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TRACE_DIR = ROOT / ".bench_traces"
# a traced window ends with the first whole batch or step past this many
# seconds: a longer one adds trace to read, not metrics to report
TRACE_SECONDS = 4.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


def devices_for(cell):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < cell.chips:
        raise NoChip(f"{cell.name} needs {cell.chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:cell.chips]


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             controls: bool = False) -> dict:
    """One run of ``cell``; returns the result object."""
    from benchmarks.chip import compiles, harness, shapes
    from benchmarks.chip import trace as trace_lib

    cache = compiles.enable_cache()
    devices = devices_for(cell)
    kind = devices[0].device_kind
    peaks = shapes.peaks_for(kind)
    log(f"device: platform={devices[0].platform} device_kind={kind} "
        f"count={len(devices)}; compilation cache {cache}")
    trace_dir = None
    if trace:
        trace_dir = str(TRACE_DIR / cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        seconds = min(seconds, TRACE_SECONDS)
    out = harness.drive_module(cell).run(cell, seed, seconds, trace_dir, T0,
                                         peaks, controls=controls)
    log(f"window: {out.window_s:.3f} s, {out.attempted} attempted, "
        f"{out.failed} failed, compilations in the window: "
        f"{out.compiles_in_window}")
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": None, "attempted": out.attempted,
              "failed": out.failed}
    if trace:
        reduced = trace_lib.reduce(trace_lib.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        out.trace = reduced
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        metrics = {}
        for name in cell.per_layer:
            value = harness.read_metric(name, out)
            if value is not None:
                metrics[name] = {"value": value, "unit": unit_of(name)}
        result["breakdown"] = reduced.breakdown()
    else:
        metrics = {name: {"value": out.e2e[name], "unit": unit_of(name)}
                   for name in cell.end_to_end}
    checks = harness.judge(cell, out.numbers)
    correct = out.failed == 0 and all(c["ok"] for c in checks.values())
    result.update(correct=correct, metrics=metrics, device=device)
    result["counters"] = out.counters
    if out.readings:
        # each control and fault judged as a run of it would be
        result["controls"] = {}
        for name, numbers in out.readings.items():
            judged = harness.judge(cell, numbers)
            result["controls"][name] = {
                "correct": all(c["ok"] for c in judged.values()),
                "checks": {k: {"value": c["value"], "limit": c["limit"]}
                           for k, c in judged.items()}}
    result["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                        for k, c in checks.items()}
    return result


def unit_of(metric: str) -> str:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == metric:
            return m["unit"]
    raise KeyError(metric)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--controls", type=int, choices=(0, 1), default=0,
                    help="also judge the low-precision control and the "
                         "faults read in the reference (not part of a "
                         "measured run)")
    args = ap.parse_args(argv)
    from benchmarks.chip import harness

    cell = harness.load_cell(args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          controls=bool(args.controls))
    except NoChip as e:
        log(str(e))
        return 2
    for name, ctl in result.get("controls", {}).items():
        log(f"control {name}: correct={ctl['correct']} " + ", ".join(
            f"{k} {c['value']!r} (limit {c['limit']!r})"
            for k, c in ctl["checks"].items()))
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
