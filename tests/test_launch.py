"""The serve and train launchers at smoke size, the depth cut, and the
compilation-cache helper."""

import math

import jax
import pytest

from repro.configs import registry
from repro.launch import compiles, serve, train


@pytest.fixture
def no_cache_change(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the launchers leave JAX's cache
    configuration alone (JAX read the variable at import, before it was
    set here, so no cache is written)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    yield
    assert jax.config.jax_compilation_cache_dir == before


def test_serve_launcher_answers_every_request(no_cache_change):
    res = serve.main(["--smoke", "--batch", "3", "--prompt-len", "16",
                      "--max-new", "4", "--max-len", "32"])
    assert len(res["outputs"]) == 3
    for p, o in zip(res["prompts"], res["outputs"]):
        assert len(p) == 16 and o[:16] == p and len(o) == 20


def test_serve_launcher_rejects_prompt_past_cache(no_cache_change):
    with pytest.raises(SystemExit):
        serve.main(["--smoke", "--prompt-len", "30", "--max-new", "4",
                    "--max-len", "32"])


def test_train_launcher_compiles_the_step_once(no_cache_change, capsys):
    res = train.main(["--smoke", "--layers", "1", "--steps", "3",
                      "--batch", "2", "--seq", "16"])
    assert res["config"].n_layers == 1
    assert res["compiles_per_step"] == [1, 0, 0]
    assert res["final_step"] == 3
    assert all(math.isfinite(m["loss"]) for m in res["metrics"])
    assert "depth cut: 1 of 2 layers" in capsys.readouterr().out


def test_with_depth_keeps_widths():
    full = registry.get("granite-3-2b")
    cut = full.with_depth(4)
    assert cut.n_layers == 4
    assert (cut.d_model, cut.n_heads, cut.d_ff, cut.vocab_size) == (
        full.d_model, full.n_heads, full.d_ff, full.vocab_size)


@pytest.mark.parametrize("arch,n_layers", [("granite-3-2b", 0),
                                           ("granite-3-2b", 41),
                                           ("zamba2-2.7b", 5)])
def test_with_depth_rejects_partial_groups(arch, n_layers):
    with pytest.raises(ValueError):
        registry.get(arch).with_depth(n_layers)


def test_cache_follows_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compiles.enable_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_defaults_to_fixed_checkout_path(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compiles.enable_cache() == str(compiles.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(compiles.CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()
    assert (compiles.CACHE_DIR.parent / "src" / "repro").is_dir()


def test_compile_log_counts_new_programs():
    f = jax.jit(lambda x: x * 3)
    five, six, seven = (jax.numpy.ones(n) for n in (5, 6, 7))
    with compiles.CompileLog() as log:
        f(five)
        f(five)
        assert log.count == 1
        f(six)
    assert log.count == 2
    assert set(log.seconds) == {"jit(<lambda>)"}
    f(seven)                             # closed: no longer listening
    assert log.count == 2
