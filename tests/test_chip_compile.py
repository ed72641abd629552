"""Compile the main path's programs for a TPU v5e that is described, not
attached: what the chip's compiler refuses (a program that does not fit
16 GiB of HBM, an unsupported op) fails here, at no chip time.  Nothing
runs, so these say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from benchmarks.chip import layer_trace
from repro.configs import registry
from repro.models import model as model_lib
from repro.optim import adamw
from repro.sharding import partition
from repro.sharding.context import use_mesh
from repro.train import train_step as ts

# what the v5e compiler lets one program use of the chip's 16 GiB
HBM_LIMIT = 15.75 * 2**30


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip: keep the cache out of these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _placed(tree, sharding):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used <= HBM_LIMIT, f"{used / 2**30:.2f} GiB"
    return used


@pytest.fixture(scope="module")
def granite(one_chip):
    """granite-3-2b at all 40 layers and published widths, as shapes."""
    model = model_lib.build(registry.get("granite-3-2b"))
    params = jax.eval_shape(model.init, jax.random.key(0))
    return model, _placed(params, one_chip)


@pytest.fixture(scope="module")
def serving(granite, one_chip):
    """The serve launcher's two programs at its default batch, prompt and
    cache length (4 requests, 256-token prompts, 1024-token cache),
    compiled once for the tests that read them."""
    model, params = granite
    batch, prompt_len, max_len = 4, 256, 1024
    cache = _placed(jax.eval_shape(lambda: model.init_cache(batch, max_len)),
                    one_chip)
    out = {}
    for program, n_tokens in (("decode_step", 1), ("prefill", prompt_len)):
        tokens = jax.ShapeDtypeStruct((batch, n_tokens), jnp.int32,
                                      sharding=one_chip)
        out[program] = jax.jit(getattr(model, program)).lower(
            params, cache, tokens, None).compile()
    return out


@pytest.mark.parametrize("program", ["decode_step", "prefill"])
def test_serving_program_compiles_for_one_chip(serving, program):
    # the bf16 parameters alone are 4.7 GiB
    assert _fits(serving[program]) > 4.5 * 2**30


@pytest.mark.parametrize("program", ["decode_step", "prefill"])
def test_serving_program_keeps_layer_scopes(serving, program):
    """The chip's compiler keeps each layer kind in the ``op_name`` of the
    instructions it emits, where a profiler trace shows them."""
    names = re.findall(r'op_name="([^"]*)"', serving[program].as_text())
    kinds = {layer_trace.kind_of(n) for n in names}
    assert {"embed", "attention", "kv_cache", "mlp", "unembed"} <= kinds


def test_train_step_compiles_for_one_chip(topo):
    """The train launcher's donated step at published widths, cut to 2
    layers and a small batch so that it compiles in seconds."""
    cfg = registry.get("granite-3-2b").with_depth(2)
    model = model_lib.build(cfg)
    opt = adamw.AdamWConfig(total_steps=3)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    shapes = jax.eval_shape(lambda k: ts.make_train_state(model, opt, k),
                            jax.random.key(0))
    shardings = partition.param_shardings(shapes, mesh)
    state = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), shapes, shardings)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (2, 512), jnp.int32, sharding=NamedSharding(mesh, P()))}
    with use_mesh(mesh):
        compiled = jax.jit(ts.make_train_step(model, opt),
                           out_shardings=(shardings, None),
                           donate_argnums=(0,)).lower(state, batch).compile()
    _fits(compiled)
    # the donated state is reused in place for the new state
    assert compiled.memory_analysis().alias_size_in_bytes > 0
