"""Compile the main path's programs for a TPU v5e that is described, not
attached: what the chip's compiler refuses (a program that does not fit
16 GiB of HBM, an unsupported op) fails here, at no chip time.  Nothing
runs, so these say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

import collections
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
from repro.serve.engine import Engine, ServeConfig
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


@pytest.fixture(scope="module")
def serve_decode(granite, one_chip):
    """The engine's own decode program, which takes the cache over
    (donated), at the serve-decode cell's shapes: 16 requests over a
    3072-token cache.  Returns the compiled program and the cache."""
    model, params = granite
    batch, max_len = 16, 3072
    engine = Engine(model, params, ServeConfig(max_batch=batch,
                                               max_len=max_len))
    cache = _placed(jax.eval_shape(lambda: model.init_cache(batch, max_len)),
                    one_chip)
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip)
    return engine.decode.lower(params, cache, tokens, None).compile(), cache


_INSTRUCTION = re.compile(r"\s*(?:ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]*)\]"
                          r"\S* ([\w\-]+)\(([^)]*)\)")


def _top_level(text: str) -> list[tuple[str, int, str, list[str]]]:
    """(opcode, output bytes, name, operand names) of every array-valued
    instruction of an optimized HLO module outside fused computations:
    the buffers the program writes to memory."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    out, computation = [], None
    for line in text.splitlines():
        if not line.startswith(" "):
            head = re.match(r"(?:ENTRY )?(%[\w.\-]+) ", line)
            computation = head.group(1) if head else computation
            continue
        m = _INSTRUCTION.match(line)
        if computation in fused or not m:
            continue
        bits = re.search(r"\d+", m.group(2))       # bf16, f32, s8; pred
        size = max(1, int(bits.group()) // 8) if bits else 1
        for d in filter(None, m.group(3).split(",")):
            size *= int(d)
        out.append((m.group(4), size, m.group(1),
                    re.findall(r"%[\w.\-]+", m.group(5))))
    return out


def test_decode_step_updates_the_cache_in_place(serve_decode):
    """The new cache is the donated one: the step writes each layer's new
    token where the cache lies and reads each layer where it lies.  Its
    only instructions with an output of a layer's cache or more are the
    2 x 40 writes of one token's K and V into the stacked cache, in place;
    no copy, slice or restack of a layer's cache is left (the scan over
    the layers made about ten per layer)."""
    compiled, cache = serve_decode
    _fits(compiled)
    k, v = cache["k"], cache["v"]
    layer_bytes = k.size // k.shape[0] * k.dtype.itemsize
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= (k.size + v.size) * k.dtype.itemsize)
    ops = _top_level(compiled.as_text())
    size = {name: n for _, n, name, _ in ops}
    big = [(op, operands) for op, n, _, operands in ops
           if n >= layer_bytes
           and op not in ("parameter", "get-tuple-element", "bitcast")]
    # a dynamic-update-slice reuses its operand's buffer and writes only
    # its update
    in_place = [op for op, operands in big if op == "dynamic-update-slice"
                and size.get(operands[1], 0) < layer_bytes]
    assert len(in_place) == 2 * 40
    assert len(big) - len(in_place) == 0, big


def _instructions(compiled) -> str:
    """The instruction lines of a compiled program, without metadata: what
    the chip runs, apart from names of source lines and scopes."""
    text = re.sub(r", metadata=\{[^}]*\}", "", compiled.as_text())
    return "\n".join(line for line in text.splitlines()
                     if re.match(r"\s*(ROOT |ENTRY )?%", line))


# sha256 of ``_instructions`` of granite-3-2b's programs, with jax 0.9.0 and
# its TPU compiler: ``decode_step`` since it reads the cache through the
# decode kernel, ``prefill`` since it attends through the causal flash
# kernel
DENSE_PROGRAMS = {
    "decode_step": "52d2397c2b466d0be839e7197f5fb0a4"
                   "374b740463e19f7bd4913f2ab5fcd1fc",
    "prefill": "e7970615d54c606e0795ead77385553c"
               "abfd2bc0c7221cdef59eeed037fe7ee9",
}


def test_dense_programs_are_unchanged(serving, serve_decode):
    import hashlib

    got = {"decode_step": _instructions(serve_decode[0]),
           "prefill": _instructions(serving["prefill"])}
    assert {k: hashlib.sha256(v.encode()).hexdigest()
            for k, v in got.items()} == DENSE_PROGRAMS


def _fusion_roots(text: str) -> dict[str, str]:
    """Fusion instruction name -> opcode of its fused computation's root."""
    roots, computation = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) ", line)
        if head and not line.startswith(" "):
            computation = head.group(1)
        root = re.match(r"\s*ROOT %[\w.\-]+ = \S+ ([\w\-]+)\(", line)
        if root:
            roots[computation] = root.group(1)
    calls = re.findall(r"(%[\w.\-]+) = \S+ fusion\(.*calls=(%[\w.\-]+)",
                       text)
    return {name: roots.get(called, "") for name, called in calls}


@pytest.fixture(scope="module")
def hybrid_decode(one_chip):
    """granite-4.0-h-micro's decode program as the engine jits it, at the
    serve-chat-b32 cell's shapes: 32 requests over a 2304-token cache."""
    model = model_lib.build(registry.get("granite-4.0-h-micro"))
    params = _placed(jax.eval_shape(model.init, jax.random.key(0)),
                     one_chip)
    batch, max_len = 32, 2304
    engine = Engine(model, params, ServeConfig(max_batch=batch,
                                               max_len=max_len))
    cache = _placed(jax.eval_shape(lambda: model.init_cache(batch, max_len)),
                    one_chip)
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip)
    return engine.decode.lower(params, cache, tokens, None).compile(), cache


def test_hybrid_decode_updates_its_state_in_place(hybrid_decode):
    """The donated cache is the new one: each of the 36 mamba layers
    writes its SSM state back where it lies (an in-place
    dynamic-update-slice fusion that computes the new state as it writes
    it) and each of the 4 attention layers its token's K and V.  No other
    instruction has an output of a layer's SSM state or more: no state is
    sliced out, copied or stacked again."""
    compiled, cache = hybrid_decode
    _fits(compiled)
    assert (compiled.memory_analysis().alias_size_in_bytes
            >= sum(a.size * a.dtype.itemsize
                   for n, a in cache.items() if n != "pos"))
    h = cache["h"]
    state_bytes = h.size // h.shape[0] * h.dtype.itemsize
    text = compiled.as_text()
    ops = _top_level(text)
    size = {name: n for _, n, name, _ in ops}
    roots = _fusion_roots(text)
    big = [(op, name, operands) for op, n, name, operands in ops
           if n >= state_bytes
           and op not in ("parameter", "get-tuple-element", "bitcast")]
    state = [name for op, name, _ in big if op == "fusion"
             and roots.get(name) == "dynamic-update-slice"
             and size[name] == h.size * h.dtype.itemsize]
    kv = [name for op, name, operands in big
          if op == "dynamic-update-slice"
          and size.get(operands[1], 0) < state_bytes]
    assert len(state) == 36
    assert len(kv) == 2 * 4
    assert len(big) == len(state) + len(kv), big


@pytest.fixture(scope="module")
def hybrid_prefill(one_chip):
    """granite-4.0-h-micro's prefill at a small size: 10 layers, 2
    prompts of 256 tokens over a 512-token cache."""
    model = model_lib.build(
        registry.get("granite-4.0-h-micro").with_depth(10))
    params = _placed(jax.eval_shape(model.init, jax.random.key(0)),
                     one_chip)
    cache = _placed(jax.eval_shape(lambda: model.init_cache(2, 512)),
                    one_chip)
    tokens = jax.ShapeDtypeStruct((2, 256), jnp.int32, sharding=one_chip)
    return jax.jit(model.prefill).lower(params, cache, tokens,
                                        None).compile()


@pytest.mark.parametrize("program", ["decode_step", "prefill"])
def test_hybrid_programs_keep_layer_scopes(hybrid_decode, hybrid_prefill,
                                           program):
    """The mamba layers' work is named ``ssm``, beside the kinds the dense
    programs show; prefill at a small size, the scopes are the same."""
    compiled = (hybrid_decode[0] if program == "decode_step"
                else hybrid_prefill)
    names = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    kinds = {layer_trace.kind_of(n) for n in names}
    assert {"embed", "ssm", "attention", "kv_cache", "mlp",
            "unembed"} <= kinds


def _kernel_calls(compiled) -> dict[tuple[str, str], int]:
    """(kernel name, layer kind of its ``op_name``) -> how many times a run
    of the program calls it: a call inside a loop's body counts once per
    trip (the trip count is the constant its condition compares with)."""
    text = compiled.as_text()
    comps: dict[str, list[str]] = {}
    name = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) ", line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif name and line.startswith(" "):
            comps[name].append(line)
        elif name and comps[name] and line not in ("", "}"):
            # a kernel's metadata breaks its instruction over lines
            comps[name][-1] += line

    def trips(cond: str) -> int:
        return max(int(n) for line in comps[cond]
                   for n in re.findall(r"s32\[\]\S* constant\((\d+)\)",
                                       line))

    def calls(comp: str) -> collections.Counter:
        out = collections.Counter()
        for line in comps[comp]:
            kernel = re.match(r"\s*%(splash_\w+?|paged_decode_attention)"
                              r"(?:\.\d+)? = .*custom-call\(", line)
            if kernel:
                op = re.search(r'op_name="([^"]*)"', line)
                out[kernel.group(1), layer_trace.kind_of(op.group(1))] += 1
            loop = re.search(r"condition=(%[\w.\-]+), body=(%[\w.\-]+)",
                             line)
            if loop:
                for k, n in calls(loop.group(2)).items():
                    out[k] += n * trips(loop.group(1))
        return out

    entry = re.search(r"^ENTRY (%[\w.\-]+) ", text, re.M).group(1)
    return dict(calls(entry))


def test_prefill_runs_the_flash_kernel_per_layer(serving):
    """Prefill from position 0 attends through the fused flash kernel:
    one forward call per layer, each under the ``attention`` scope, and
    no other kernel."""
    assert _kernel_calls(serving["prefill"]) == {
        ("splash_mqa_fwd_no_residuals", "attention"): 40}


def test_decode_step_reads_the_cache_through_the_decode_kernel(
        serve_decode):
    """At serve-decode's shapes each layer's one-token attention is one
    call of the Pallas decode kernel, under the ``attention`` scope, which
    reads the stacked cache where it lies (the in-place test above finds
    no copy or slice of a layer's cache)."""
    assert _kernel_calls(serve_decode[0]) == {
        ("paged_decode_attention", "attention"): 40}


def test_hybrid_decode_fits_and_runs_the_decode_kernel(hybrid_decode):
    """granite-4.0-h-micro's decode at serve-chat-b32's 32 x 2304 fits one
    chip, its 4 attention layers each reading through the kernel."""
    _fits(hybrid_decode[0])
    assert _kernel_calls(hybrid_decode[0]) == {
        ("paged_decode_attention", "attention"): 4}


def test_wide_head_decode_fits_and_runs_the_decode_kernel(one_chip):
    """glm4-9b-20L's decode at serve-prefill's cache of 8192 positions:
    heads of 128 (2 KV heads, 16 query heads each), which the kernel
    reads position-major, as the chip keeps them, in one call per
    layer."""
    model = model_lib.build(registry.get("glm4-9b").with_depth(20))
    params = _placed(jax.eval_shape(model.init, jax.random.key(0)),
                     one_chip)
    engine = Engine(model, params, ServeConfig(max_batch=1, max_len=8192))
    cache = _placed(jax.eval_shape(lambda: model.init_cache(1, 8192)),
                    one_chip)
    tokens = jax.ShapeDtypeStruct((1, 1), jnp.int32, sharding=one_chip)
    compiled = engine.decode.lower(params, cache, tokens, None).compile()
    _fits(compiled)
    assert _kernel_calls(compiled) == {
        ("paged_decode_attention", "attention"): 20}


def test_long_prompt_prefill_fits(one_chip):
    """glm4-9b's serve-prefill shapes, cut to 2 layers: the longest
    prompt, 6144 tokens, over an 8192-token cache, through the kernel."""
    model = model_lib.build(registry.get("glm4-9b").with_depth(2))
    params = _placed(jax.eval_shape(model.init, jax.random.key(0)),
                     one_chip)
    cache = _placed(jax.eval_shape(lambda: model.init_cache(1, 8192)),
                    one_chip)
    tokens = jax.ShapeDtypeStruct((1, 6144), jnp.int32, sharding=one_chip)
    compiled = jax.jit(model.prefill).lower(params, cache, tokens,
                                            None).compile()
    _fits(compiled)
    assert _kernel_calls(compiled) == {
        ("splash_mqa_fwd_no_residuals", "attention"): 2}


@pytest.fixture(scope="module")
def train_step(topo):
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
        return jax.jit(ts.make_train_step(model, opt),
                       out_shardings=(shardings, None),
                       donate_argnums=(0,)).lower(state, batch).compile()


def test_train_step_compiles_for_one_chip(train_step):
    _fits(train_step)
    # the donated state is reused in place for the new state
    assert train_step.memory_analysis().alias_size_in_bytes > 0


def test_train_step_runs_the_flash_kernel_both_ways(train_step):
    """Per layer, the kernel's forward (saving its output and log-sum-exp,
    which the remat policy keeps, so the backward pass does not run it
    again) and its fused backward, all under the ``attention`` scope."""
    assert _kernel_calls(train_step) == {
        ("splash_mqa_fwd_residuals", "attention"): 2,
        ("splash_mqa_dkv_no_residuals", "attention"): 2}


# sha256 of ``_instructions`` of the other pinned programs, with jax 0.9.0
# and its TPU compiler: granite-4.0-h-micro's decode step at the
# serve-chat-b32 shapes since it reads its K/V through the decode kernel,
# and, as compiled before one schedule walked every family's layer stack,
# its prefill at ``hybrid_prefill``'s shapes and granite-3-2b's 2-layer
# train step
PINNED_PROGRAMS = {
    "hybrid_decode": "48dc711130b39f759f1d9fc9eb32c917"
                     "3d9e1bf60339098a7079694860c8e9c8",
    "hybrid_prefill": "7d1dddcf4be2c94cf941e15457b221ea"
                      "0f11524b34b8498c183e97a1f2a42d14",
    "train_step": "28729543994ab0f09c7bf5bd60787207"
                  "9b67ff9feb33e019d60a6fdfcbe40366",
}


@pytest.mark.parametrize("program", sorted(PINNED_PROGRAMS))
def test_pinned_programs_are_unchanged(request, program):
    import hashlib

    compiled = request.getfixturevalue(program)
    if program == "hybrid_decode":
        compiled = compiled[0]
    digest = hashlib.sha256(_instructions(compiled).encode()).hexdigest()
    assert digest == PINNED_PROGRAMS[program]
