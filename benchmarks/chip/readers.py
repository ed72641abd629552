"""What the per-layer metric files (``metrics/<name>.py``) share.

Each reader takes the run (``harness.Outcome``, with ``trace`` the reduced
profiler trace of the traced window) and returns a number, or ``None``
where the run has nothing to read.  Program kinds are the jitted function
names that the trace's ``XLA Modules`` line carries: ``prefill`` and
``decode_step`` (the serving engine), ``step`` (the train step).
"""

from __future__ import annotations

import statistics


def idle_share(run) -> float | None:
    return run.trace.idle_share if run.trace else None


def mean_device_ms(run, kind: str) -> float | None:
    times = run.trace.device_ms(kind) if run.trace else []
    return statistics.fmean(times) if times else None


def mean_host_gap_ms(run, kind: str) -> float | None:
    gaps = run.trace.host_gap_ms(kind) if run.trace else []
    return statistics.fmean(gaps) if gaps else None


def device_ms_per_ktok(run, kind: str, counter: str) -> float | None:
    times = run.trace.device_ms(kind) if run.trace else []
    tokens = run.counters.get(counter, 0)
    return sum(times) / (tokens / 1000) if times and tokens else None


def mfu(run) -> float | None:
    """Least chip time for the traced window's required work (operations
    over the peak rate or bytes over the peak bandwidth, whichever is
    longer, per program) over the window's length times the chips."""
    least = run.counters.get("least_s", 0.0)
    if not run.trace or least <= 0:
        return None
    return least / (run.trace.window_s * run.chips)
