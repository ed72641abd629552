import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, numpy as np, jax.numpy as jnp

if jax.device_count() < 8:
    # this platform ignored xla_force_host_platform_device_count (e.g. a
    # real-accelerator runtime with fewer devices); parent test skips
    print("SKIP_NEED_MULTI_DEVICE")
    raise SystemExit(0)

from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
from repro.configs import registry
from repro.models import model as model_lib
from repro.optim import adamw
from repro.sharding import partition
from repro.sharding.context import use_mesh
from repro.train import train_step as ts

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
cfg = dataclasses.replace(
    registry.get("glm4-9b").reduced(), d_model=64, n_heads=4, n_kv_heads=2,
    d_ff=128, overlap="shared_bus", constrain_activations=True)
model = model_lib.build(cfg)
opt = adamw.AdamWConfig(lr=1e-3, total_steps=10)
state = ts.make_train_state(model, opt, jax.random.key(0))
sh = partition.param_shardings(jax.eval_shape(lambda: state), mesh)
step = jax.jit(ts.make_train_step(model, opt), out_shardings=(sh, None))
batch = {"tokens": jnp.asarray(np.random.default_rng(0).integers(0, 256, (8, 32), np.int32))}
bs = {"tokens": NamedSharding(mesh, P("data", None))}
with use_mesh(mesh):
    lowered = jax.jit(ts.make_train_step(model, opt), in_shardings=(sh, bs), out_shardings=(sh, None)).lower(jax.eval_shape(lambda: state), jax.eval_shape(lambda: batch))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    print("collective-permute count:", hlo.count(" collective-permute("))
    # and actually run it for numerics
    state2, metrics = jax.jit(ts.make_train_step(model, opt))(state, batch)
    print("loss:", float(metrics["loss"]))
    cfg0 = dataclasses.replace(cfg, overlap="none")
    m0 = model_lib.build(cfg0)
    _, metrics0 = jax.jit(ts.make_train_step(m0, opt))(state, batch)
    print("loss (no overlap):", float(metrics0["loss"]))
    assert abs(float(metrics["loss"]) - float(metrics0["loss"])) < 1e-2
    print("OVERLAP_TRAIN_OK")
