"""The trace reduction, on a small trace recorded on a TPU v5e.

The recorded window holds three rounds of two jitted programs: ``lambda``
(a 1024x1024 matmul chain) under a ``step_call`` span, then a 2 ms sleep,
then a small reduction under a ``batch_fetch`` span followed by a 1 ms
sleep.  So the chip is busy for a small share of the window and the
longest idle gaps fall where the host slept."""

import pathlib

import pytest

from benchmarks.chip import trace

FIXTURE = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(str(FIXTURE))


def test_window_and_busy(reduced):
    assert reduced.n_chips == 1
    assert 0.005 < reduced.window_s < 1.0
    assert 0 < reduced.busy_s < reduced.window_s
    assert 0.5 < reduced.idle_share < 1.0
    union = trace.union([iv for ivs in reduced.programs.values()
                         for iv in ivs])
    assert reduced.busy_s == pytest.approx(sum(e - s for s, e in union))


def test_programs_by_kind(reduced):
    assert set(reduced.programs) == {"_lambda"}
    times = reduced.device_ms("_lambda")
    assert len(times) == 6
    assert all(0 < t < 5 for t in times)
    gaps = reduced.host_gap_ms("_lambda")
    assert len(gaps) == 5 and all(g > 0 for g in gaps)


def test_gaps_are_named_by_host_span(reduced):
    names = {n for n, _ in reduced.gaps}
    assert names <= set(trace.SPANS) | {"outside_spans"}
    assert "window" in names
    idle = sum(s for _, s in reduced.gaps)
    assert idle == pytest.approx(reduced.window_s - reduced.busy_s)


def test_breakdown(reduced):
    b = reduced.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(k.startswith("_lambda:") for k, _ in b["device_ops"])
    assert all(v > 0 for _, v in b["device_ops"] + b["idle_gaps"])


def test_union_and_covered():
    merged = trace.union([(3, 4), (0, 1), (0.5, 2)])
    assert merged == [(0, 2), (3, 4)]
    assert trace.covered(merged, 1, 3.5) == pytest.approx(1.5)
    assert trace.module_kind("jit_decode_step(123)") == "decode_step"
