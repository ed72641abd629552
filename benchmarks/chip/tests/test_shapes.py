"""The shape arithmetic against counts made by hand from the published
sizes, and the peaks table."""

import pytest

from benchmarks.chip import shapes
from benchmarks.chip.harness import HERE


def sizes(name):
    return shapes.load_sizes(HERE / "configs" / f"{name}.json")


@pytest.mark.parametrize("name,params,kv", [
    # 40 x (4.19M q + 1.05M k + 1.05M v + 4.19M o + 50.33M mlp + 4096 norm)
    # + 100.67M tied embedding + 2048 final norm
    ("granite-3-2b", 2_533_531_648, 81_920),
    ("granite-3-2b-4L", 343_957_504, 8_192),
    # 20 x (35.65M attention + 168.30M mlp + 8192 norm) + 2 x 620.76M
    ("glm4-9b-20L", 5_320_642_560, 20_480),
])
def test_parameters_and_kv_bytes(name, params, kv):
    s = sizes(name)
    assert s.params == params
    assert s.kv_bytes_per_token == kv
    assert s.weight_bytes == 2 * params


def test_granite_published_size():
    assert sizes("granite-3-2b").params / 1e9 == pytest.approx(2.533, abs=1e-3)
    assert sizes("glm4-9b-20L").params / 1e9 == pytest.approx(5.32, abs=1e-2)


def test_train_step_flops():
    # 3 x 2 x (4 x 60.82M + 100.67M) per token x 16384 tokens, plus
    # attention: 3 x 4 x 4 layers x 2048 x (2048 x 2049 / 2) x 8 rows
    s = sizes("granite-3-2b-4L")
    flops, _ = shapes.train_work(s, 8, 2048)
    matmul = 3 * 2 * (4 * 60_817_408 + 49_155 * 2048) * 16384
    attn = 3 * 4 * 4 * 2048 * (2048 * 2049 / 2) * 8
    assert flops == pytest.approx(matmul + attn, rel=1e-12)
    assert flops == pytest.approx(3.55e13, rel=0.01)


def test_prefill_and_decode_work():
    g = sizes("glm4-9b-20L")
    flops, nbytes = shapes.prefill_work(g, [6144])
    assert flops == pytest.approx(5.6e13, rel=0.01)
    assert nbytes == g.weight_bytes + 6144 * 20_480
    s = sizes("granite-3-2b")
    flops, nbytes = shapes.decode_work(s, [100, 200])
    assert nbytes == s.weight_bytes + 81_920 * (101 + 201)
    # a decode step of two requests is bound by bytes on a v5e
    peaks = shapes.peaks_for("TPU v5 lite")
    assert shapes.least_seconds((flops, nbytes), peaks) == nbytes / 819e9


def test_peaks_table():
    peaks = shapes.peaks_for("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        shapes.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        shapes.peaks_for("source")
