"""Layer-kind scopes: every kind of work the programs do is named in the
``op_name`` metadata of its compiled instructions, where the benchmark's
trace reduction (``benchmarks/chip/layer_trace.py``) finds it."""

import re

import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import layer_trace
from repro.configs import registry
from repro.models import layers
from repro.models import model as model_lib
from repro.optim import adamw
from repro.train import train_step as ts

SERVING = {"embed", "attention", "kv_cache", "mlp", "unembed"}


def _kinds(compiled) -> set[str]:
    return {layer_trace.kind_of(n) for n in
            re.findall(r'op_name="([^"]*)"', compiled.as_text())}


def _compiled(arch: str, program: str):
    model = model_lib.build(registry.get(arch).reduced())
    params = jax.eval_shape(model.init, jax.random.key(0))
    if program == "step":
        opt = adamw.AdamWConfig(total_steps=3)
        state = jax.eval_shape(
            lambda: ts.make_train_state(model, opt, jax.random.key(0)))
        batch = {"tokens": jax.ShapeDtypeStruct((2, 32), jnp.int32)}
        return jax.jit(ts.make_train_step(model, opt)).lower(
            state, batch).compile()
    cache = jax.eval_shape(lambda: model.init_cache(2, 64))
    tokens = jax.ShapeDtypeStruct((2, 1 if program == "decode_step" else 8),
                                  jnp.int32)
    return jax.jit(getattr(model, program)).lower(
        params, cache, tokens, None).compile()


@pytest.mark.parametrize("arch,program,kinds", [
    ("granite-3-2b", "decode_step", SERVING),
    ("granite-3-2b", "prefill", SERVING),
    ("granite-3-2b", "step", {"embed", "attention", "mlp", "unembed",
                              "loss", "optimizer"}),
    ("qwen2-moe-a2.7b", "decode_step", {"embed", "attention", "kv_cache",
                                        "moe", "unembed"}),
    ("falcon-mamba-7b", "decode_step", {"embed", "ssm", "unembed"}),
])
def test_compiled_programs_name_their_layer_kinds(arch, program, kinds):
    found = _kinds(_compiled(arch, program))
    assert kinds <= found, kinds - found


def test_backward_pass_keeps_the_forward_scope():
    """Gradients of the attention and MLP are found under their kinds."""
    text = _compiled("granite-3-2b", "step").as_text()
    backward = [n for n in re.findall(r'op_name="([^"]*)"', text)
                if "transpose(" in n]
    assert {"attention", "mlp"} <= {layer_trace.kind_of(n)
                                    for n in backward}


def test_benchmark_reads_the_program_vocabulary():
    assert layers.SCOPES == layer_trace.KINDS


@pytest.mark.parametrize("op_name,kind", [
    ("jit(decode_step)/while/body/closed_call/attention/kv_cache/"
     "dynamic_update_slice", "kv_cache"),
    ("jit(step)/transpose(jvp())/while/body/checkpoint/mlp/dot_general",
     "mlp"),
    ("jit(step)/transpose(jvp(unembed))/dot_general", "unembed"),
    ("jit(step)/jit(train_loss)/reduce_sum", "other"),
    ("jit(decode_step)/while/body/dynamic_slice", "other"),
    ("", "other"),
])
def test_kind_of(op_name, kind):
    assert layer_trace.kind_of(op_name) == kind
