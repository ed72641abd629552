"""The per-layer reduction (``layer_trace.py``): the exact split of idle
time and the join of operations to layer kinds, on made-up intervals and
on two traces recorded on a TPU v5e.

``small.xplane.pb`` (see ``test_trace.py``) has no program spans and no
scopes; ``layers.xplane.pb`` (``record_layers.py``) holds a 2-layer model
served through ``Engine.generate`` (3 new tokens) and trained one step
through ``Trainer.run`` inside one ``window``."""

import pathlib

import pytest

from benchmarks.chip import layer_trace, trace

DATA = pathlib.Path(__file__).parent / "data"
SMALL = DATA / "small.xplane.pb"
LAYERS = DATA / "layers.xplane.pb"


def test_gaps():
    busy = [(1.0, 2.0), (3.0, 5.0), (6.0, 7.0)]
    assert layer_trace.gaps(busy, 0.0, 6.5) == [(0.0, 1.0), (2.0, 3.0),
                                                (5.0, 6.0)]
    assert layer_trace.gaps(busy, 1.5, 2.5) == [(2.0, 2.5)]
    assert layer_trace.gaps([], 0.0, 1.0) == [(0.0, 1.0)]


def test_split_idle_is_exact():
    """An idle stretch that spans several nested spans is cut at their
    edges, each piece going to the innermost; nothing is lost or doubled."""
    spans = [("window", 0.0, 10.0), ("generate", 1.0, 9.0),
             ("engine.read_tokens", 2.0, 3.0), ("engine.decode", 3.0, 3.5),
             ("engine.read_tokens", 6.0, 7.0)]
    idle = [(0.5, 2.5), (2.9, 3.2), (5.0, 6.5), (9.5, 11.0)]
    got = layer_trace.split_idle(idle, spans)
    assert got == pytest.approx({
        "window": 0.5 + 0.5, "generate": 1.0 + 1.0,
        "engine.read_tokens": 0.5 + 0.1 + 0.5, "engine.decode": 0.2,
        layer_trace.OUTSIDE: 1.0})
    assert sum(got.values()) == pytest.approx(sum(b - a for a, b in idle))


def test_op_names_on_small_trace():
    names = layer_trace.op_names(SMALL.read_bytes())
    assert names and set(names.values()) == {"jit(<lambda>)/dot_general:",
                                             "jit(<lambda>)/reduce_sum:"}
    programs = {pid for pid, _ in names}
    assert len(programs) == 2


@pytest.fixture(scope="module")
def layers():
    return layer_trace.reduce(str(LAYERS))


def test_layers_fixture_size():
    assert LAYERS.stat().st_size <= 300 * 1024


def test_program_spans(layers):
    """One generate call of 3 new tokens, then one train step; besides the
    model's programs, the engine's eager sampling and slicing run as
    programs of their own."""
    n = {k: len(v) for k, v in layers.spans.items()}
    assert n["engine.generate"] == n["engine.admit"] == 1
    assert n["engine.prefill"] == 1
    assert n["engine.read_tokens"] == n["engine.decode"] == 3
    assert n["trainer.step"] == 1
    assert n["trainer.data_wait"] == 2    # the step's, and the one after
    assert {k: layers.calls[k] for k in ("prefill", "decode_step",
                                         "step")} == {
        "prefill": 1, "decode_step": 3, "step": 1}
    assert layers.calls["_argmax"] == 4   # one per sample


def test_kinds_add_up_to_program_time(layers):
    """Each operation is counted once, in its program: a program's kinds,
    with its time between operations, add up to its device time, and that
    rest is never negative.  (At full size it is under 0.1% of the decode
    step, the prefill and the train step; at this size up to a fifth.)"""
    for program, seconds in layers.program_s.items():
        kinds = layers.kinds[program]
        assert sum(kinds.values()) == pytest.approx(seconds, rel=1e-9)
        assert kinds[layer_trace.BETWEEN] > -1e-12, program
    for program in ("decode_step", "prefill", "step"):
        assert (layers.kinds[program][layer_trace.BETWEEN]
                < 0.25 * layers.program_s[program])
    assert {"attention", "kv_cache", "mlp", "unembed"} <= set(
        layers.kinds["decode_step"])
    assert {"attention", "mlp", "unembed", "loss", "optimizer"} <= set(
        layers.kinds["step"])
    assert layers.ops["decode_step:dynamic_update_slice.28"][1] == "kv_cache"


def test_small_trace_has_no_scopes():
    small = layer_trace.reduce(str(SMALL))
    assert set(small.kinds["_lambda"]) == {layer_trace.OTHER,
                                           layer_trace.BETWEEN}


def test_idle_is_attributed_exactly(layers):
    assert sum(layers.idle.values()) == pytest.approx(layers.idle_s)
    outer = trace.reduce(str(LAYERS))
    assert layers.window_s == pytest.approx(outer.window_s)
    assert layers.idle_s == pytest.approx(outer.window_s - outer.busy_s,
                                          rel=1e-6)
    assert {"engine.read_tokens", "engine.decode"} <= set(layers.idle)


def test_clock_offset(layers):
    assert 0 < layers.offset_us < 1e5
    assert layers.offset_spread_us >= 0
