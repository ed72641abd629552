"""Reduce one profiler trace (``.xplane.pb``) of the traced window.

What a TPU trace holds (looked at by hand on a v5e, jax 0.9): a plane
``/device:TPU:<n>`` per chip, whose line ``XLA Modules`` has one event per
program execution, named ``jit_<function>(<fingerprint>)`` and carrying a
``run_id`` stat, and whose line ``XLA Ops`` has one event per operation;
and a plane ``/host:CPU`` whose lines hold the host threads, among them
the benchmark's own ``TraceAnnotation`` spans and the runtime's
``CompleteCallbacks`` events, which carry the ``run_id`` of the execution
they complete.  The device clock and the host clock of one trace differ
by an offset of about a millisecond; it is taken as the least gap from a
program's end on the device to the host's completion of the same run,
which puts each program as late as the host allows.

The window is the host span named ``window``.  Busy time is the union of
the program intervals inside it, per chip; idle time is the rest.  Each
idle gap is named by the innermost benchmark span, on the thread that
holds the window, that covers its middle.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

WINDOW_SPAN = "window"
# the benchmark's own host spans, around its calls into the program
SPANS = ("window", "generate", "step_call")
# ops whose event spans the ops of their body, which have events of their own
CONTAINERS = {"while", "conditional", "call"}


def module_kind(event_name: str) -> str:
    """``jit_decode_step(123)`` -> ``decode_step``."""
    base = event_name.split("(", 1)[0]
    return base[4:] if base.startswith("jit_") else base


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: list[tuple[float, float]], lo: float, hi: float) -> float:
    """Length of [lo, hi] covered by sorted, disjoint intervals."""
    total = 0.0
    i = max(bisect.bisect_right([s for s, _ in merged], lo) - 1, 0)
    for s, e in merged[i:]:
        if s >= hi:
            break
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over chips
    programs: dict[str, list[tuple[float, float]]]   # kind -> intervals, s
    ops: dict[str, float]               # "<kind>:<op>" -> device seconds
    gaps: list[tuple[str, float]]       # (host span, idle seconds)
    n_chips: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def device_ms(self, kind: str) -> list[float]:
        return [(e - s) * 1e3 for s, e in self.programs.get(kind, [])]

    def host_gap_ms(self, kind: str) -> list[float]:
        """Device-idle time between consecutive programs of one kind."""
        mine = sorted(self.programs.get(kind, []))
        busy = union([iv for ivs in self.programs.values() for iv in ivs])
        return [((b[0] - a[1]) - covered(busy, a[1], b[0])) * 1e3
                for a, b in zip(mine, mine[1:])]

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        idle = collections.Counter()
        for name, sec in self.gaps:
            idle[name] += sec
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle.most_common(10)]}


def _stats(event) -> dict:
    return dict(event.stats)


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    if not devices:
        raise ValueError(f"{path}: no TPU device plane")
    host = pd.find_plane_with_name("/host:CPU")
    spans: list[tuple[str, float, float, int]] = []
    complete: dict[int, float] = {}
    for n_line, line in enumerate(host.lines):
        for ev in line.events:
            if ev.name in SPANS:
                spans.append((ev.name, ev.start_ns, ev.end_ns, n_line))
            elif ev.name == "CompleteCallbacks":
                rid = _stats(ev).get("run_id")
                if rid is not None:
                    complete.setdefault(int(rid), ev.start_ns)
    windows = [(s, e, ln) for n, s, e, ln in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} '{WINDOW_SPAN}' spans")
    main_line = windows[0][2]

    per_chip_busy, programs, ops, offsets = [], collections.defaultdict(
        list), collections.Counter(), []
    raw_modules = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        mods = [(ev.name, ev.start_ns, ev.end_ns, _stats(ev).get("run_id"))
                for ev in lines["XLA Modules"].events]
        raw_modules.append(mods)
        for _, _, end, rid in mods:
            if rid is not None and int(rid) in complete:
                offsets.append(complete[int(rid)] - end)
    offset = min(offsets) if offsets else 0.0
    w0, w1 = windows[0][0] - offset, windows[0][1] - offset

    for plane, mods in zip(devices, raw_modules):
        ivs = []
        for name, s, e, _ in mods:
            s, e = max(s, w0), min(e, w1)
            if e > s:
                ivs.append((s, e))
                if plane is devices[0]:
                    programs[module_kind(name)].append(
                        ((s - w0) * 1e-9, (e - w0) * 1e-9))
        merged = union(ivs)
        per_chip_busy.append(sum(e - s for s, e in merged) * 1e-9)
        if plane is devices[0]:
            starts = [s for _, s, _, _ in mods]
            op_line = {ln.name: ln for ln in plane.lines}.get("XLA Ops")
            for ev in (op_line.events if op_line else ()):
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e <= s:
                    continue
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                kind = module_kind(mods[i][0]) if i >= 0 else "?"
                op = ev.name.split(" = ", 1)[0].lstrip("%")
                if op.split(".")[0] not in CONTAINERS:
                    ops[f"{kind}:{op}"] += (e - s) * 1e-9
            gaps = []
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            host_spans = [(n, s - offset, e - offset)
                          for n, s, e, ln in spans if ln == main_line]
            for lo, hi in zip(edges[::2], edges[1::2]):
                if hi <= lo:
                    continue
                mid = (lo + hi) / 2
                inside = [(e - s, n) for n, s, e in host_spans
                          if s <= mid <= e]
                gaps.append((min(inside)[1] if inside else "outside_spans",
                             (hi - lo) * 1e-9))
    return Reduced(window_s=(w1 - w0) * 1e-9,
                   busy_s=sum(per_chip_busy) / len(per_chip_busy),
                   programs=dict(programs), ops=dict(ops), gaps=gaps,
                   n_chips=len(devices))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]
