"""What a run needs from ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, mix, cell or per-layer
metric is a file found by its name:

* ``BENCHMARK.json``'s ``configs[].file``: the sizes (``configs/``);
* ``traffic/<mix>.json``: the mix, read by ``traffic.py``;
* ``limits/<cell>.json``: the limit of each number that decides
  ``correct`` (PERF.md gives the readings each was set from);
* ``metrics/<metric>.py``: a reader ``read(run) -> float | None``;
* ``drive_<kind>.py``: the loop that runs a mix's ``kind`` (serve or train).
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import math
import pathlib

from benchmarks.chip import shapes, traffic

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file
    sizes: shapes.Sizes
    mix: dict               # the traffic file
    end_to_end: list[str]   # names of the end-to-end metrics it reports
    per_layer: list[str]    # names of the per-layer metrics it reports
    limits: dict            # number -> {"limit": ..., ...}


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())

    def reports(metric: dict) -> bool:
        return name in metric.get("workloads", cells)

    e2e = [m["name"] for m in bench["end_to_end"] if reports(m)]
    per_layer = [m["name"] for m in bench["per_layer"] if reports(m)
                 and m["moves"] in e2e]
    limits_file = HERE / "limits" / f"{name}.json"
    return Cell(name=name, chips=w["chips"], config=config,
                sizes=shapes.Sizes.from_config(config),
                mix=traffic.load(w["traffic"]), end_to_end=e2e,
                per_layer=per_layer,
                limits=json.loads(limits_file.read_text()))


def drive_module(cell: Cell):
    return importlib.import_module(
        f"benchmarks.chip.drive_{cell.mix['kind']}")


def read_metric(name: str, run) -> float | None:
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def judge(cell: Cell, numbers: dict[str, float]) -> dict[str, dict]:
    """Each compared number beside its limit; a number that is missing or
    not finite fails."""
    out = {}
    for key, lim in cell.limits.items():
        value = numbers.get(key, math.nan)
        out[key] = {"value": value, "limit": lim["limit"],
                    "ok": math.isfinite(value) and value <= lim["limit"]}
    return out


@dataclasses.dataclass
class Outcome:
    """What a drive module hands back from one run."""
    e2e: dict[str, float]             # every end-to-end value it measured
    counters: dict[str, float]        # work counted in the (traced) window
    window_s: float
    attempted: int
    failed: int
    numbers: dict[str, float]         # the numbers that decide correct
    readings: dict[str, dict]         # numbers of each control and fault,
                                      # if asked
    memory_peak_bytes: int
    compiles_in_window: int
    trace: object | None = None       # trace.Reduced of a traced run
    chips: int = 1
