"""Unit tests for dry-run mechanics that don't need 512 devices."""

import jax


def test_collective_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %ag = bf16[16,4096,1152]{2,1,0} all-gather(%p0), replica_groups={}
  %ar.1 = f32[256,128]{1,0} all-reduce(%x), to_apply=%sum
  %cp-start = (f32[8,2]{1,0}, f32[8,2]{1,0}) collective-permute-start(%y)
  %cp-done = f32[8,2]{1,0} collective-permute-done(%cp-start)
  %rs = bf16[64]{0} reduce-scatter(%z), dimensions={0}
  %a2a = s8[1024]{0} all-to-all(%w), dimensions={0}
  %not_a_coll = f32[2,2]{1,0} add(%a, %b)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"]["bytes"] == 16 * 4096 * 1152 * 2
    assert out["all-gather"]["count"] == 1
    assert out["all-reduce"]["bytes"] == 256 * 128 * 4
    # async pair counted once (at -start), tuple shape -> max element
    assert out["collective-permute"]["count"] == 1
    assert out["collective-permute"]["bytes"] == 8 * 2 * 4
    assert out["reduce-scatter"]["bytes"] == 64 * 2
    assert out["all-to-all"]["bytes"] == 1024
    assert "add" not in out


def test_layer_group_sizes():
    from repro.configs import registry
    from repro.launch.dryrun import layer_group
    assert layer_group(registry.get("gemma3-1b")) == 6
    assert layer_group(registry.get("gemma2-9b")) == 2
    assert layer_group(registry.get("zamba2-2.7b")) == 6
    assert layer_group(registry.get("llama-3.2-vision-11b")) == 5
    assert layer_group(registry.get("llama4-maverick-400b-a17b")) == 2
    assert layer_group(registry.get("falcon-mamba-7b")) == 1


def test_shape_applicability():
    from repro.configs import registry
    from repro.configs.base import SHAPES, shape_applicable
    long = SHAPES["long_500k"]
    runs = {a: shape_applicable(registry.get(a), long)[0]
            for a in registry.ARCHS}
    assert runs["falcon-mamba-7b"] and runs["zamba2-2.7b"] \
        and runs["gemma3-1b"]
    for a in ("musicgen-medium", "glm4-9b", "gemma2-9b", "granite-3-2b",
              "qwen2-moe-a2.7b", "llama4-maverick-400b-a17b",
              "llama-3.2-vision-11b"):
        assert not runs[a], a
    # every other shape applies to every arch
    for s in ("train_4k", "prefill_32k", "decode_32k"):
        for a in registry.ARCHS:
            assert shape_applicable(registry.get(a), SHAPES[s])[0]


def test_input_specs_are_abstract():
    """ShapeDtypeStruct stand-ins only — no device allocation."""
    from repro.configs import registry
    from repro.configs.base import SHAPES
    from repro.launch import specs
    from repro.models import model as model_lib
    cfg = registry.get("glm4-9b")
    model = model_lib.build(cfg)
    cache, inputs = specs.decode_input_specs(cfg, model,
                                             SHAPES["decode_32k"])
    for leaf in jax.tree.leaves((cache, inputs)):
        assert isinstance(leaf, jax.ShapeDtypeStruct), type(leaf)
    assert cache["k"].shape == (40, 128, 32768, 2, 128)


def test_mesh_factory_shapes():
    """Mesh axis names/sizes via AbstractMesh (no 512 devices needed)."""
    from jax.sharding import AbstractMesh
    single = AbstractMesh((16, 16), ("data", "model"))
    multi = AbstractMesh((2, 16, 16), ("pod", "data", "model"))
    assert dict(zip(single.axis_names, single.shape.values())) == {
        "data": 16, "model": 16}
    assert dict(zip(multi.axis_names, multi.shape.values())) == {
        "pod": 2, "data": 16, "model": 16}


def test_roofline_model_flops_sanity():
    from benchmarks.roofline import _param_counts
    from repro.configs import registry
    # published sizes within 20%
    sizes = {"gemma2-9b": 9e9, "glm4-9b": 9e9, "falcon-mamba-7b": 7e9,
             "zamba2-2.7b": 2.7e9, "granite-3-2b": 2.5e9,
             "gemma3-1b": 1.3e9}
    for arch, want in sizes.items():
        total, active = _param_counts(registry.get(arch))
        assert 0.7 * want < total < 1.45 * want, (arch, total)
    # llama4: ~400B total / ~17B active
    total, active = _param_counts(registry.get("llama4-maverick-400b-a17b"))
    assert 3.4e11 < total < 4.6e11, total
    assert 1.2e10 < active < 2.2e10, active
    # qwen2-moe: 14.3B total / 2.7B active
    total, active = _param_counts(registry.get("qwen2-moe-a2.7b"))
    assert 1.0e10 < total < 1.8e10, total
    assert 2.0e9 < active < 3.6e9, active
