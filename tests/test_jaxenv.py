"""The shared JAX settings of ``repro.jaxenv``: the engine's float64 scope
and where the entry points keep the persistent compilation cache."""

import jax
import jax.numpy as jnp

from repro import jaxenv


def test_x64_scope_is_float64_inside_only():
    with jaxenv.x64():
        assert jnp.asarray(1.0).dtype == jnp.float64
    assert jnp.asarray(1.0).dtype == jnp.float32


def test_compile_cache_follows_the_environment(monkeypatch, tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, JAX's own setting stands and
    nothing is configured or created."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    monkeypatch.setattr(jaxenv, "DEFAULT_CACHE_DIR", tmp_path / "fixed")
    before = jax.config.jax_compilation_cache_dir
    assert jaxenv.init_compile_cache() == tmp_path / "env"
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "fixed").exists()


def test_compile_cache_defaults_to_a_fixed_directory(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jaxenv, "DEFAULT_CACHE_DIR", tmp_path / "fixed")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert jaxenv.init_compile_cache() == tmp_path / "fixed"
        assert (tmp_path / "fixed").is_dir()
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "fixed")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_default_cache_directory_is_inside_the_checkout():
    root = jaxenv.DEFAULT_CACHE_DIR.parent
    assert (root / "src" / "repro" / "jaxenv.py").is_file()
    assert jaxenv.DEFAULT_CACHE_DIR.name in (root / ".gitignore").read_text()
