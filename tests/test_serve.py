"""Serving engine integration tests across model families."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.models import model as model_lib
from repro.serve.engine import Engine, ServeConfig


def _engine(arch, dtype=None, **kw):
    cfg = registry.get(arch).reduced()
    if dtype:
        cfg = dataclasses.replace(cfg, dtype=dtype)
    model = model_lib.build(cfg)
    params = model.init(jax.random.key(0))
    return cfg, Engine(model, params, ServeConfig(max_batch=4, max_len=96,
                                                  **kw))


@pytest.mark.parametrize("arch", ["granite-3-2b", "falcon-mamba-7b",
                                  "zamba2-2.7b", "qwen2-moe-a2.7b",
                                  "granite-4.0-h-micro"])
def test_generate_batch(arch):
    cfg, eng = _engine(arch)
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(2, cfg.vocab_size, size=n))
               for n in (3, 7, 5, 9)]
    outs = eng.generate(prompts, max_new=8)
    assert len(outs) == 4
    for p, o in zip(prompts, outs):
        assert o[:len(p)] == p            # prompt preserved
        assert len(o) > len(p)            # something generated
        assert all(0 <= t < cfg.vocab_size for t in o)


def test_greedy_deterministic():
    cfg, eng = _engine("granite-3-2b", temperature=0.0)
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(2, cfg.vocab_size, size=6))]
    a = eng.generate(prompts, max_new=6)
    b = eng.generate(prompts, max_new=6)
    assert a == b


@pytest.mark.parametrize("arch,prompt_len,dtype", [
    ("granite-3-2b", 5, None),
    # local/global layers with an 8-token window and a logit softcap: a
    # 12-token prompt puts early tokens outside the local layers' window.
    # In float32: in bfloat16 two of its logits lie half a bfloat16 step
    # apart, closer than the two programs' roundings agree
    ("gemma2-9b", 12, "float32"),
    # audio: media frames ahead of the tokens, no rotary embeddings
    ("musicgen-medium", 5, None),
    # mamba-2 and NoPE attention layers; 11 tokens span an SSD chunk (8)
    # and part of the next.  In float32, as gemma2's
    ("granite-4.0-h-micro", 11, "float32"),
])
def test_greedy_matches_teacher_forcing(arch, prompt_len, dtype):
    """Engine decode, over its donated cache, must agree with argmax over
    the forward logits."""
    cfg, eng = _engine(arch, dtype=dtype, temperature=0.0, eos_token=-1)
    rng = np.random.default_rng(2)
    prompt = list(rng.integers(2, cfg.vocab_size, size=prompt_len))
    out = eng.generate([prompt], max_new=6)[0]
    assert len(out) == prompt_len + 6
    model = eng.model
    import jax.numpy as jnp
    # teacher-force the generated sequence and check each next-token argmax
    batch = {"tokens": jnp.asarray([out])}
    if cfg.n_media_tokens:
        batch["media"] = jnp.zeros(
            (1, cfg.n_media_tokens, cfg.media_embed_dim), jnp.float32)
    logits = model.forward(eng.params, batch)
    for t in range(len(prompt) - 1, len(out) - 1):
        want = int(jnp.argmax(logits[0, t]))
        assert out[t + 1] == want, f"mismatch at position {t}"


def test_chip_smoke_replay_chains_the_donated_cache():
    """``chip_smoke.py``'s replay passes each returned cache on to the
    next donated call; at CPU sizes it replays the served tokens and its
    logits match the forward pass."""
    import chip_smoke

    cfg, eng = _no_eos_engine()
    prompts = [[2 + i, 3, 4, 5, 6] for i in range(3)]
    outs = eng.generate(prompts, max_new=6)
    gap, gap_prefill = chip_smoke.cached_logit_gap(eng, prompts, outs)
    assert gap <= chip_smoke.LOGIT_TOL
    assert gap_prefill <= chip_smoke.LOGIT_TOL


def test_eos_stops_slot():
    cfg, eng = _engine("granite-3-2b", temperature=0.0)
    # craft a prompt; whatever gets generated, force its first generated
    # token to be EOS by setting eos to that token
    prompt = [5, 9, 4]
    out0 = eng.generate([prompt], max_new=8)[0]
    first_tok = out0[len(prompt)]
    eng.cfg = ServeConfig(max_batch=4, max_len=96, temperature=0.0,
                          eos_token=first_tok)
    out = eng.generate([prompt], max_new=8)[0]
    assert out == prompt + [first_tok]


def _no_eos_engine():
    # an end token the model cannot produce: every request runs max_new
    return _engine("granite-3-2b", eos_token=-1)


@pytest.mark.parametrize("batch,max_new", [(1, 2), (4, 5)])
def test_generate_emits_engine_spans(batch, max_new):
    """One admit and one prefill per call, then one read_tokens and one
    decode per decode iteration, each tagged with the call and step."""
    from _profile import spans

    cfg, eng = _no_eos_engine()
    prompts = [[2 + i, 3, 4] for i in range(batch)]
    eng.generate(prompts, max_new=1)          # compile outside the trace
    found = spans(lambda: eng.generate(prompts, max_new=max_new),
                  "engine.")
    names = [n for n, _ in found]
    assert names == (["engine.generate", "engine.admit", "engine.prefill"]
                     + ["engine.read_tokens", "engine.decode"] * max_new)
    assert all(stats["batch"] == 2 for _, stats in found)
    steps = [stats["step"] for n, stats in found
             if n == "engine.read_tokens"]
    assert steps == list(range(max_new))


@pytest.mark.parametrize("batch,max_new", [(1, 2), (4, 5), (3, 1)])
def test_engine_counters(batch, max_new):
    """One read of the position per call and one of the batch's tokens per
    decode iteration, one decode_step per iteration, and the last one's
    token never kept."""
    cfg, eng = _no_eos_engine()
    prompts = [[2 + i, 3, 4] for i in range(batch)]
    for calls in (1, 2):
        eng.generate(prompts, max_new=max_new)
        c = eng.metrics.snapshot()["counters"]
        assert c["engine.decode_steps"] == calls * max_new
        assert c["engine.host_reads"] == calls * (max_new + 1)
        assert c["engine.decode_steps_kept"] == calls * (max_new - 1)


@pytest.mark.parametrize("batch,max_new", [(1, 2), (4, 5)])
def test_engine_counts_decode_kv_positions(batch, max_new):
    """Per decode step, the cache's ``max_len`` positions held, and as many
    read: off the TPU attention reads the whole cache."""
    cfg, eng = _no_eos_engine()
    prompts = [[2 + i, 3, 4] for i in range(batch)]
    for calls in (1, 2):
        eng.generate(prompts, max_new=max_new)
        c = eng.metrics.snapshot()["counters"]
        assert c["engine.decode_kv_positions_held"] == (
            calls * max_new * eng.cfg.max_len)
        assert (c["engine.decode_kv_positions_read"]
                == c["engine.decode_kv_positions_held"])


@pytest.mark.parametrize("arch,blk", [("granite-3-2b", 32),
                                      ("granite-4.0-h-micro", 8),
                                      ("falcon-mamba-7b", None)])
def test_engine_counts_the_blocks_a_kernel_reads(arch, blk):
    """Where the decode kernel reads the cache in blocks, each step counts
    its live positions (the prompt, then one more per step) rounded up to
    a block; a stack with no attention counts nothing."""
    cfg, eng = _engine(arch, eos_token=-1)
    eng._decode_block = lambda batch: blk
    prompt, max_new = [2, 3, 4, 5, 6], 40
    eng.generate([prompt] * 2, max_new=max_new)
    c = eng.metrics.snapshot()["counters"]
    lives = range(len(prompt) + 1, len(prompt) + max_new + 1)
    want = sum(-(-n // blk) * blk for n in lives) if blk else 0
    assert c["engine.decode_kv_positions_read"] == want
    assert c["engine.decode_kv_positions_held"] == (
        max_new * eng.cfg.max_len if blk else 0)


@pytest.mark.parametrize("arch,kv,state", [
    ("granite-3-2b", 2 * 2 * 4 * 96 * 2 * 16 * 2, 0),
    # 2 attention layers; 2 mamba layers' conv windows (3 x 144 bf16) and
    # SSM states (8 heads x 16 x 8 float32), per request
    ("granite-4.0-h-micro", 2 * 2 * 4 * 96 * 2 * 16 * 2,
     2 * 4 * (3 * 144 * 2 + 8 * 16 * 8 * 4)),
])
def test_engine_records_its_cache_bytes(arch, kv, state):
    """As each batch's cache is built, the bytes it holds for keys and
    values and for recurrent state."""
    cfg, eng = _engine(arch)
    eng.generate([[2, 3, 4]] * 4, max_new=1)
    g = eng.metrics.snapshot()["gauges"]
    assert g["engine.cache_bytes.kv"]["last"] == kv
    assert g["engine.cache_bytes.state"]["last"] == state


def test_engine_counters_stop_at_eos():
    """A batch whose requests all ended stops before another
    decode_step."""
    cfg, eng = _engine("granite-3-2b", temperature=0.0)
    prompt = [5, 9, 4]
    first = eng.generate([prompt], max_new=4)[0][len(prompt)]
    eng = Engine(eng.model, eng.params,
                 ServeConfig(max_batch=4, max_len=96, eos_token=first))
    assert eng.generate([prompt], max_new=4) == [prompt + [first]]
    c = eng.metrics.snapshot()["counters"]
    assert c["engine.host_reads"] == 2
    assert c["engine.decode_steps"] == 0
    assert c["engine.decode_steps_kept"] == 0
