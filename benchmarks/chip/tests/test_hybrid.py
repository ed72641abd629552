"""The layer-pattern hybrid's cell (granite-4.0-h-micro.serve-chat-b32):
its files load, its counts of work match a hand count, its weights are
the ones the reference draws again, and ``drive_serve_hybrid.py`` runs a small
configuration end to end on the CPU, where a sound run passes and the
float8 control fails."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import (harness, hybrid_shapes, hybrid_weights, run,
                             weights)
from benchmarks.chip.hybrid_shapes import HybridSizes
from benchmarks.chip.tests.cells import cut

CELL = "granite-4.0-h-micro.serve-chat-b32"
SMALL = {"hidden_size": 256, "num_hidden_layers": 4,
         "layer_types": ["mamba", "attention", "mamba", "attention"],
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
         "intermediate_size": 512, "vocab_size": 4096, "mamba_n_heads": 8,
         "mamba_d_head": 64, "mamba_d_state": 16, "mamba_chunk_size": 64}
# float8's error grows with depth and width: at 4 x 256 or 8 x 512 the
# control's logit_gap reads 0.24-0.62 on the CPU, at 16 x 512 0.73-0.87,
# against 2.19 at the cell's own size (PERF.md)
DEEP = {**SMALL, "hidden_size": 512, "num_hidden_layers": 16,
        "layer_types": (["mamba"] * 3 + ["attention"] + ["mamba"] * 4) * 2,
        "intermediate_size": 1024, "vocab_size": 8192, "mamba_n_heads": 16,
        "mamba_d_state": 32}
SERVE = dict(batch=4, new_tokens=16, max_len=320, check_requests=4,
             prompt={"median": 128, "min": 64, "max": 256, "round_to": 64},
             cycle=2)
SEED = 2 ** 31 + 29


def test_cell_loads():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1
    assert cell.end_to_end == ["setup_s", "decode_tokens_per_s"]
    assert set(cell.per_layer) == {
        "device_idle_share.decode", "decode_step_device_ms",
        "decode_host_gap_ms", "mfu.decode", "ssm_device_ms.decode",
        "ssm_roofline_share.decode", "ssm_device_ms_per_ktok.prefill"}
    assert harness.drive_module(cell).__name__.endswith("drive_serve_hybrid")
    s = HybridSizes.from_config(cell.config)
    assert (s.layers, s.n_mamba, s.n_attn, s.d_inner, s.conv_dim,
            s.in_proj_width) == (40, 36, 4, 4096, 4352, 8512)
    assert cell.mix["max_len"] == 2048 + cell.mix["new_tokens"]


def test_counts_match_a_hand_count():
    s = HybridSizes.from_config(harness.load_cell(CELL).config)
    # in_proj 2048 x 8512, out_proj 4096 x 2048, conv 4 x 4352 and its
    # bias, the gated norm's 4096 gains in bfloat16; 3 x 64 float32
    mixer = (2048 * 8512 + 4096 * 2048 + 4 * 4352 + 4352 + 4096) * 2 \
        + 3 * 64 * 4
    assert s.mixer_bytes == mixer == 51_694_848
    # 64 heads x 64 x 128 float32 state and 3 x 4352 bfloat16 conv rows
    assert s.layer_state_bytes == 64 * 64 * 128 * 4 + 3 * 4352 * 2
    assert s.state_bytes_per_seq == 36 * 2_123_264
    assert s.kv_bytes_per_token == 4 * 2 * 8 * 64 * 2 == 8192
    assert hybrid_shapes.ssm_decode_bytes(s, 32) == 36 * (
        mixer + 2 * 32 * 2_123_264)
    mlp, norms = 3 * 2048 * 8192, 2 * 2048
    attn = 2048 * 64 * (2 * 32 + 2 * 8)
    params = (36 * (mlp + norms) + 4 * (attn + mlp + norms)
              + 100352 * 2048 + 2048)
    assert s.weight_bytes == params * 2 + 36 * mixer
    assert 3.18e9 < (s.weight_bytes - 36 * 3 * 64 * 4) / 2 < 3.20e9
    # the weights the benchmark draws are those bytes
    tree = jax.eval_shape(lambda k: hybrid_weights.serving_weights(
        k, s, 0.002), weights.root_key(0))
    assert sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(tree)) == s.weight_bytes


def test_layer_is_its_slice_of_the_stack():
    cell = cut(CELL, SMALL, **SERVE)
    s = HybridSizes.from_config(cell.config)
    key = weights.root_key(SEED)
    whole = hybrid_weights.serving_weights(key, s, 0.002)
    layer = hybrid_weights.mamba_layer_weights(key, s, 2)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a[1], b),
                 whole["blocks"], layer)
    attn = weights.layer_weights(key, s, 3)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a[1], b),
                 whole["attn_blocks"], attn)
    assert whole["blocks"]["mixer"]["A_log"].dtype == jnp.float32


@pytest.mark.usefixtures("on_cpu")
def test_small_run_passes_and_control_fails():
    cell = cut(CELL, DEEP, **SERVE)
    res = run.run_cell(cell, SEED, 0.05, False, controls=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 4 and res["failed"] == 0
    c = res["counters"]
    assert c["checked_tokens"] >= 4 * 16
    # 14 mamba layers' float32 state and conv rows, 2 attention layers'
    # K/V over the 320-token cache, for 4 requests
    assert c["engine.cache_bytes.state"] == 14 * 4 * (
        16 * 64 * 32 * 4 + 3 * (1024 + 2 * 32) * 2)
    assert c["engine.cache_bytes.kv"] == 2 * 2 * 4 * 320 * 2 * 64 * 2
    assert c["repeat_share"] < 0.5
    control = res["controls"]["control"]
    assert control["correct"] is False
    assert (control["checks"]["logit_gap"]["value"]
            > cell.limits["logit_gap"]["limit"])


def _state_reset(e):
    """Each decode step forgets the SSM state it wrote."""
    decode = e.decode

    def reset(p, cache, tok, m):
        logits, cache = decode(p, cache, tok, m)
        return logits, {**cache, "h": jnp.zeros_like(cache["h"])}
    e.decode = reset


def _altered_token(e):
    sample = e._sample
    e._sample = lambda logits, key: (sample(logits, key) + 1) % \
        e.model.cfg.vocab_size


@pytest.mark.usefixtures("on_cpu")
@pytest.mark.parametrize("fault", [_state_reset, _altered_token])
def test_faults_fail(monkeypatch, fault):
    from benchmarks.chip import drive_serve_hybrid

    make = drive_serve_hybrid.engine

    def engine(*args):
        e = make(*args)
        fault(e)
        return e
    monkeypatch.setattr(drive_serve_hybrid, "engine", engine)
    cell = cut(CELL, DEEP, **SERVE)
    res = run.run_cell(cell, SEED, 0.05, False)
    assert not res["correct"]
    assert (res["checks"]["logit_gap"]["value"]
            > cell.limits["logit_gap"]["limit"])
