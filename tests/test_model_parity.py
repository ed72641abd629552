"""Every architecture's logits against those recorded in
``tests/golden_logits.npz`` (``tests/capture_logits.py``): forward,
prefill and three decode steps at the reduced config in float32."""

import numpy as np
import pytest

from capture_logits import GOLDEN_PATH, logits
from repro.configs import registry


@pytest.fixture(scope="module")
def golden():
    with np.load(GOLDEN_PATH) as f:
        return dict(f)


@pytest.mark.parametrize("arch", registry.ARCHS)
def test_logits_match_the_recorded_ones(golden, arch):
    got = logits(arch)
    assert set(got) == {"forward", "prefill", "decode"}
    for name, value in got.items():
        np.testing.assert_allclose(value, golden[f"{arch}/{name}"],
                                   rtol=1e-4, atol=1e-5, err_msg=name)
