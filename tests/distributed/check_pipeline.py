"""Pipeline-parallel correctness: 4 stages x 6 microbatches == sequential."""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

if jax.device_count() < 4:
    # this platform ignored xla_force_host_platform_device_count (e.g. a
    # real-accelerator runtime with fewer devices); parent test skips
    print("SKIP_NEED_MULTI_DEVICE")
    raise SystemExit(0)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import AxisType  # noqa: E402

from repro.train.pipeline import pipeline  # noqa: E402


def main():
    mesh = jax.make_mesh((4,), ("pipe",), axis_types=(AxisType.Auto,))
    rng = np.random.default_rng(0)
    n_stages, n_micro, mb, d = 4, 6, 2, 16
    w = jnp.asarray(rng.normal(size=(n_stages, d, d)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(n_stages, d)) * 0.1, jnp.float32)
    xs = jnp.asarray(rng.normal(size=(n_micro, mb, d)), jnp.float32)

    def f(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    got = pipeline(f, {"w": w, "b": b}, xs, mesh)

    # sequential oracle
    want = xs
    for s in range(n_stages):
        want = jnp.tanh(want @ w[s] + b[s])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    print("pipeline matches sequential oracle")

    # the hand-off really is collective-permute (the bus), and the schedule
    # runs S+M-1 ticks
    hlo = jax.jit(lambda p, x: pipeline(f, p, x, mesh)).lower(
        {"w": w, "b": b}, xs).compile().as_text()
    assert "collective-permute" in hlo
    print("PIPELINE_OK")


if __name__ == "__main__":
    main()
