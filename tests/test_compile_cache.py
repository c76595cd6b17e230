"""launch.compile_cache: JAX_COMPILATION_CACHE_DIR wins when set; otherwise
a fixed directory inside the checkout, the same on every call."""
import jax

from repro.launch import compile_cache


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert calls == []


def test_fixed_checkout_path_otherwise(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    second = compile_cache.enable_compile_cache()
    repo = compile_cache.Path(__file__).resolve().parents[1]
    assert first == second == str(repo / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", first)] * 2
