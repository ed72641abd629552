"""Multi-device numerics check for the SharedBus overlap module.

Run in a subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8
(see test_overlap.py).  Exits non-zero on any mismatch.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402

if jax.device_count() < 8:
    # this platform ignored xla_force_host_platform_device_count (e.g. a
    # real-accelerator runtime with fewer devices); parent test skips
    print("SKIP_NEED_MULTI_DEVICE")
    raise SystemExit(0)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core.overlap import collective_matmul as cm  # noqa: E402
from repro.core.overlap import compression  # noqa: E402


def main():
    assert jax.device_count() == 8, jax.devices()
    mesh = jax.make_mesh((8,), ("model",))
    rng = np.random.default_rng(0)
    B, T, D, F = 2, 64, 32, 48
    x = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
    w1 = jnp.asarray(rng.normal(size=(D, F)).astype(np.float32))
    w2 = jnp.asarray(rng.normal(size=(F, D)).astype(np.float32))

    # --- ag_matmul == plain matmul ---
    got = np.asarray(cm.ag_matmul(x, w1, mesh))
    want = np.asarray(x @ w1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    print("ag_matmul OK")

    # --- matmul_rs == plain matmul (reassociated sum) ---
    h = jnp.asarray(rng.normal(size=(B, T, F)).astype(np.float32))
    got = np.asarray(cm.matmul_rs(h, w2, mesh))
    want = np.asarray(h @ w2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    print("matmul_rs OK")

    # --- full overlapped FFN ---
    got = np.asarray(cm.overlapped_ffn(x, w1, w1, w2, mesh, jax.nn.silu))
    want = np.asarray((jax.nn.silu(x @ w1) * (x @ w1)) @ w2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
    print("overlapped_ffn OK")

    # --- HLO really uses collective-permute (the bus), not all-gather ---
    lowered = jax.jit(lambda a, b: cm.ag_matmul(a, b, mesh)).lower(x, w1)
    hlo = lowered.compile().as_text()
    assert "collective-permute" in hlo, "expected ring collective-permute"
    print("HLO uses collective-permute OK")

    # --- compressed gradient all-reduce with error feedback ---
    g = jnp.asarray(rng.normal(size=(8, 128)).astype(np.float32))
    e0 = jnp.zeros_like(g)

    def body(gl, el):
        return compression.psum_compressed(gl, el, "data")

    mesh2 = jax.make_mesh((8,), ("data",))
    fn = jax.jit(jax.shard_map(body, mesh=mesh2,
                              in_specs=(P("data"), P("data")),
                              out_specs=(P("data"), P("data"))))
    mean, err = fn(g, e0)
    mean = np.asarray(mean)
    # every shard's mean equals the global mean (up to int8 quantization)
    want = np.asarray(g).reshape(8, 1, 128).mean(0)
    for r in range(8):
        np.testing.assert_allclose(mean[r], want[0], rtol=0.05, atol=0.05)
    # error feedback: residual equals quantization error exactly
    assert np.isfinite(np.asarray(err)).all()
    print("psum_compressed OK")

    # error feedback convergence: mean of quantized streams -> true mean
    true = np.asarray(g).mean(0)
    acc = np.zeros_like(true)
    el = e0
    for _ in range(64):
        m, el = fn(g, el)
        acc += np.asarray(m)[0]
    np.testing.assert_allclose(acc / 64, true, rtol=2e-3, atol=2e-3)
    print("error-feedback convergence OK")


if __name__ == "__main__":
    main()
    print("ALL_OVERLAP_CHECKS_PASSED")
