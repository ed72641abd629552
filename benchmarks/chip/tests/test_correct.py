"""What decides ``correct``, at a size a CPU test run can hold.

A sound run passes with the cell's own limits; the low-precision control
(the reference in float8 in the program's place) and every fault the cell
can have, planted in the program underneath a whole run, fail.  On the
chip the same comparisons run at the cells' own sizes (PERF.md gives the
readings the limits were set from)."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import program, run
from benchmarks.chip.tests.cells import cut

SMALL = {"hidden_size": 256, "num_hidden_layers": 2,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
         "intermediate_size": 512, "vocab_size": 4096}
# training's numbers depend on how far a bfloat16 weight moves in one
# AdamW step against its rounding step, which depends on the fan-in: at
# this width that ratio is near the cell's own
WIDER = {**SMALL, "hidden_size": 1024, "num_attention_heads": 16,
         "num_key_value_heads": 4, "intermediate_size": 4096,
         "vocab_size": 8192}
SERVE = dict(batch=4, new_tokens=16, max_len=512, check_requests=4,
             prompt={"median": 128, "min": 64, "max": 256, "round_to": 64},
             cycle=2)
# the loss gap shrinks with the tokens a step averages over: at 4 x 512 the
# sound runs read near the cell's own (8 x 2048) readings
TRAIN = dict(batch=4, seq=512)
SEED = 2 ** 31 + 17


def serve_cell():
    return cut("granite-3-2b.serve-decode", SMALL, **SERVE)


def train_cell():
    return cut("granite-3-2b-4L.train-8x2k", WIDER, **TRAIN)


def once(cell, **kw):
    return run.run_cell(cell, SEED, 0.05, False, **kw)


def failed_checks(result):
    return [k for k, c in result["checks"].items()
            if not c["value"] <= c["limit"]]


pytestmark = pytest.mark.usefixtures("on_cpu")


def test_serve_sound_run_passes_and_control_fails():
    cell = serve_cell()
    res = once(cell, controls=True)
    assert res["correct"], res["checks"]
    assert res["counters"]["checked_tokens"] >= 4 * 16
    limit = cell.limits["logit_gap"]["limit"]
    control = res["controls"]["control"]
    assert control["correct"] is False
    assert control["checks"]["logit_gap"]["value"] > limit


def test_train_sound_run_passes_and_control_fails():
    cell = train_cell()
    res = once(cell, controls=True)
    assert res["correct"], res["checks"]
    control = res["controls"]["control"]
    assert control["correct"] is False
    assert any(c["value"] > c["limit"] for c in control["checks"].values())
    assert set(res["controls"]) == {"control", "half_batch"}


def _engine_with(patch):
    make = program.engine

    def engine(cell, seed):
        e = make(cell, seed)
        patch(e)
        return e
    return engine


def _altered_token(e):
    sample = e._sample

    def altered(logits, key):
        return (sample(logits, key) + 1) % e.model.cfg.vocab_size
    e._sample = altered


def _state_unchanged(e):
    decode = e.decode
    e.decode = lambda p, cache, tok, m: (decode(p, cache, tok, m)[0], cache)


def _half_batch(e):
    prefill = e.prefill

    def half(p, cache, tokens, m):
        n = tokens.shape[0] // 2
        sub = jax.tree.map(lambda a: a[:, :n] if a.ndim > 1 else a, cache)
        logits, sub = prefill(p, sub, tokens[:n], m)
        tile = jax.tree.map(lambda a: jnp.concatenate([a, a], 1)
                            if a.ndim > 1 else a, sub)
        return jnp.concatenate([logits, logits]), tile
    e.prefill = half


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged,
                                   _half_batch])
def test_serve_faults_fail(monkeypatch, fault):
    monkeypatch.setattr(program, "engine", _engine_with(fault))
    res = once(serve_cell())
    assert not res["correct"]
    assert failed_checks(res) == ["logit_gap"]


def _unchanged_step(model, opt_cfg, settings):
    def step(state, batch):
        return state, {"loss": model.train_loss(state["params"], batch)}
    return step


def _half_batch_step(model, opt_cfg, settings):
    from repro.train import train_step as ts
    whole = ts_make(model, opt_cfg, settings)

    def step(state, batch):
        n = batch["tokens"].shape[0] // 2
        return whole(state, {"tokens": batch["tokens"][:n]})
    return step


from repro.train import train_step as _ts  # noqa: E402

ts_make = _ts.make_train_step


@pytest.mark.parametrize("fault", [_unchanged_step, _half_batch_step])
def test_train_faults_fail(monkeypatch, fault):
    monkeypatch.setattr(_ts, "make_train_step", fault)
    res = once(train_cell())
    assert not res["correct"]
    assert failed_checks(res)
