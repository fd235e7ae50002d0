"""Where the persistent compilation cache lives: the environment decides,
else one fixed directory in the checkout (never a per-run path)."""
import pathlib

from repro.launch import compile_cache


def test_environment_places_the_cache(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)


def test_default_cache_is_fixed_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = pathlib.Path(compile_cache.__file__).resolve().parents[3]
    assert compile_cache.compile_cache_dir() == str(root / ".jax_cache")
    assert compile_cache.compile_cache_dir() == \
        compile_cache.compile_cache_dir()
