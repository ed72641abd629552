"""Per-layer readings of one traced window, from what the program puts in
the trace itself: its host spans (``engine.*``, ``trainer.*``) and the
layer-kind scopes in each operation's ``op_name`` metadata.

It reads the same ``.xplane.pb`` as ``trace.py`` and aligns host and
device clocks the same way (the least lag from a program's end on the
device to the host's ``CompleteCallbacks`` of the same run), and adds
three things ``trace.py`` does not do:

* Idle time is split exactly: each stretch of device-idle time in the
  window is cut where host spans start or end, and each piece goes to the
  innermost span that covers it among ``trace.SPANS`` and the program's
  spans on the window's thread, or to ``outside_spans``.  The pieces sum
  to the window's idle time.
* Each operation gets a layer kind: the innermost name of ``KINDS`` in the
  ``op_name`` of its instruction, ``other`` where none is there; a
  program's time that no operation covers is ``between_ops``, so its kinds
  add up to its device time.  A TPU
  trace keeps that ``op_name`` as the ``tf_op`` stat of the operation's
  event metadata on the device plane (looked at by hand on a v5e, jax
  0.9), keyed with the ``program_id`` that the module's name carries as
  ``jit_<name>(<program_id>)``.  For a fusion it is the fusion's own
  metadata, which XLA takes from the instruction the fusion was formed
  around.  jax's ``ProfileData`` does not show event metadata, so
  ``op_names`` reads it from the protobuf wire format directly.
* The clock offset comes with its spread: the 10th percentile of the
  callback lags minus the least, which says how far host spans and device
  events can be trusted to line up.

A compiled program loaded from JAX's persistent cache keeps the metadata
of the program that was cached, since the cache key leaves metadata out
(``jax_compilation_cache_include_metadata_in_key``): a run that reads
kinds must not load programs cached by code with other scopes.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import statistics

from benchmarks.chip import trace

# the layer kinds the program names with ``jax.named_scope``
# (``repro.models.layers.SCOPES``)
KINDS = ("embed", "attention", "kv_cache", "mlp", "moe", "ssm", "unembed",
         "loss", "optimizer")
OTHER = "other"
# device time of a program that no operation event covers: the chip
# between operations, and loop control outside a loop's body
BETWEEN = "between_ops"
PROGRAM_SPANS = ("engine.", "trainer.")
OUTSIDE = "outside_spans"

_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def kind_of(op_name: str) -> str:
    """The innermost layer kind named in an ``op_name`` path, such as
    ``jit(step)/transpose(jvp(mlp))/dot_general`` -> ``mlp``."""
    for word in reversed(_WORD.findall(op_name)):
        if word in KINDS:
            return word
    return OTHER


# --- the protobuf wire format, as far as event metadata needs it ------------

def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        out |= (b & 0x7F) << shift
        i += 1
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of one message: an int for a
    varint or fixed-width field, a memoryview for a length-delimited one."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_value(entry) -> memoryview:
    return next((v for f, v in _fields(entry) if f == 2), memoryview(b""))


def _str(value) -> str:
    return bytes(value).decode()


def op_names(data: bytes) -> dict[tuple[int, str], str]:
    """``(program_id, event name) -> op_name`` of every operation on the
    TPU device planes of a serialized ``XSpace``.

    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 (map of
    XEventMetadata: name = 2, stats = 5), .stat_metadata = 5 (map of
    XStatMetadata: id = 1, name = 2); XStat.metadata_id = 1,
    .uint64_value = 3, .int64_value = 4, .str_value = 5, .ref_value = 7
    (a string kept as the name of another stat metadata)."""
    out = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        fields = list(_fields(plane))
        name = _str(next((v for f, v in fields if f == 2), b""))
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for f, entry in fields:
            if f == 5:
                meta = dict(_fields(_map_value(entry)))
                stat_names[meta.get(1, 0)] = _str(meta.get(2, b""))
        for f, entry in fields:
            if f != 4:
                continue
            meta = list(_fields(_map_value(entry)))
            ev_name = _str(next((v for k, v in meta if k == 2), b""))
            stats = {}
            for k, stat in meta:
                if k != 5:
                    continue
                s = dict(_fields(stat))
                key = stat_names.get(s.get(1))
                if 7 in s:
                    stats[key] = stat_names.get(s[7], "")
                elif 5 in s:
                    stats[key] = _str(s[5])
                else:
                    stats[key] = s.get(3, s.get(4))
            if "tf_op" in stats and stats.get("program_id") is not None:
                out[(stats["program_id"], ev_name)] = stats["tf_op"]
    return out


# --- the exact split of idle time --------------------------------------------

def split_idle(idle: list[tuple[float, float]],
               spans: list[tuple[str, float, float]]) -> dict[str, float]:
    """Seconds of each idle interval under each span: every interval is cut
    at the span edges inside it, and each piece goes to the shortest span
    that covers it (spans on one thread nest), or to ``OUTSIDE``."""
    out: dict[str, float] = collections.Counter()
    spans = sorted(spans, key=lambda s: s[1])
    starts = [s for _, s, _ in spans]
    for lo, hi in idle:
        near = [sp for sp in spans[:bisect.bisect_left(starts, hi)]
                if sp[2] > lo]
        cuts = sorted({lo, hi, *(x for _, s, e in near for x in (s, e)
                                 if lo < x < hi)})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inside = [(e - s, n) for n, s, e in near if s <= mid <= e]
            out[min(inside)[1] if inside else OUTSIDE] += b - a
    return dict(out)


def gaps(busy: list[tuple[float, float]], lo: float,
         hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] that sorted, disjoint ``busy`` leaves
    free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


# --- the reduction ------------------------------------------------------------

@dataclasses.dataclass
class Layers:
    window_s: float
    idle_s: float                          # device-idle time in the window
    idle: dict[str, float]                 # span name -> idle seconds under it
    spans: dict[str, list[float]]          # span name -> durations (s) of the
                                           # spans wholly inside the window
    program_s: dict[str, float]            # program kind -> device seconds
    calls: dict[str, int]                  # program kind -> executions begun
                                           # in the window
    kinds: dict[str, dict[str, float]]     # program kind -> layer kind ->
                                           # device seconds of its operations,
                                           # and BETWEEN
    ops: dict[str, tuple[float, str]]      # "<program>:<op>" -> (device
                                           # seconds, layer kind)
    offset_us: float                       # device clock = host clock - this
    offset_spread_us: float

    def per_call_ms(self, program: str, *kinds: str) -> float | None:
        n = self.calls.get(program, 0)
        if not n or program not in self.kinds:
            return None
        return sum(self.kinds[program].get(k, 0.0) for k in kinds) / n * 1e3

    def idle_per_call_ms(self, span: str, program: str) -> float | None:
        n = self.calls.get(program, 0)
        return self.idle.get(span, 0.0) / n * 1e3 if n else None


def _fingerprint(module: str) -> int | None:
    m = re.search(r"\((\d+)\)$", module)
    return int(m.group(1)) if m else None


def reduce(path: str) -> Layers:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    pd = ProfileData.from_serialized_xspace(data)
    device = next((p for p in pd.planes
                   if p.name.startswith("/device:TPU:")), None)
    if device is None:
        raise ValueError(f"{path}: no TPU device plane")
    host = pd.find_plane_with_name("/host:CPU")
    spans, complete = [], {}
    for n_line, line in enumerate(host.lines):
        for ev in line.events:
            if ev.name in trace.SPANS or ev.name.startswith(PROGRAM_SPANS):
                spans.append((ev.name, ev.start_ns, ev.end_ns, n_line))
            elif ev.name == "CompleteCallbacks":
                rid = dict(ev.stats).get("run_id")
                if rid is not None:
                    complete.setdefault(int(rid), ev.start_ns)
    windows = [(s, e, ln) for n, s, e, ln in spans if n == trace.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} '{trace.WINDOW_SPAN}' "
                         f"spans")
    lines = {ln.name: ln for ln in device.lines}
    mods = sorted((ev.start_ns, ev.end_ns, ev.name,
                   dict(ev.stats).get("run_id"))
                  for ev in lines["XLA Modules"].events)
    lags = sorted(complete[int(rid)] - end for _, end, _, rid in mods
                  if rid is not None and int(rid) in complete)
    offset = lags[0] if lags else 0.0
    spread = (statistics.quantiles(lags, n=10, method="inclusive")[0]
              - lags[0] if len(lags) > 1 else 0.0)
    w0, w1 = windows[0][0] - offset, windows[0][1] - offset
    main = [(n, s - offset, e - offset) for n, s, e, ln in spans
            if ln == windows[0][2]]

    program_s, calls = collections.Counter(), collections.Counter()
    busy = []
    for s, e, name, _ in mods:
        lo, hi = max(s, w0), min(e, w1)
        if hi > lo:
            busy.append((lo, hi))
            program_s[trace.module_kind(name)] += (hi - lo) * 1e-9
        if w0 <= s < w1:
            calls[trace.module_kind(name)] += 1
    busy = trace.union(busy)
    idle = gaps(busy, w0, w1)

    names = op_names(data)
    starts = [s for s, _, _, _ in mods]
    kinds: dict[str, dict[str, float]] = collections.defaultdict(
        collections.Counter)
    ops: dict[str, tuple[float, str]] = {}
    for ev in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
        lo, hi = max(ev.start_ns, w0), min(ev.end_ns, w1)
        op = ev.name.split(" = ", 1)[0].lstrip("%")
        i = bisect.bisect_right(starts, ev.start_ns) - 1
        if hi <= lo or i < 0 or op.split(".")[0] in trace.CONTAINERS:
            continue
        module = mods[i][2]
        kind = kind_of(names.get((_fingerprint(module), ev.name), ""))
        program = trace.module_kind(module)
        kinds[program][kind] += (hi - lo) * 1e-9
        seconds, _ = ops.get(f"{program}:{op}", (0.0, kind))
        ops[f"{program}:{op}"] = (seconds + (hi - lo) * 1e-9, kind)
    for program, seconds in program_s.items():
        kinds[program][BETWEEN] = seconds - sum(kinds[program].values())

    durations = collections.defaultdict(list)
    for n, s, e in main:
        if w0 <= s and e <= w1:
            durations[n].append((e - s) * 1e-9)
    return Layers(
        window_s=(w1 - w0) * 1e-9,
        idle_s=sum(e - s for s, e in idle) * 1e-9,
        idle={k: v * 1e-9 for k, v in split_idle(idle, main).items()},
        spans=dict(durations), program_s=dict(program_s), calls=dict(calls),
        kinds={k: dict(v) for k, v in kinds.items()}, ops=ops,
        offset_us=offset * 1e-3, offset_spread_us=spread * 1e-3)
