"""The entry points' persistent compilation cache: an externally set
``JAX_COMPILATION_CACHE_DIR`` wins and nothing else is set in code;
otherwise a fixed ``.jax_cache/`` at the checkout root."""
import jax

from repro.launch import compile_cache


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_dir_is_used_and_nothing_is_set(monkeypatch, tmp_path):
    calls = _record_updates(monkeypatch)
    monkeypatch.setenv(compile_cache.ENV_CACHE_DIR, str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert calls == []


def test_default_dir_is_fixed_at_checkout_root(monkeypatch):
    calls = _record_updates(monkeypatch)
    monkeypatch.delenv(compile_cache.ENV_CACHE_DIR, raising=False)
    path = compile_cache.use_compile_cache()
    assert path == str(compile_cache.CHECKOUT_ROOT / ".jax_cache")
    assert (compile_cache.CHECKOUT_ROOT / "src" / "repro").is_dir()
    assert calls == [("jax_compilation_cache_dir", path)]
    assert compile_cache.use_compile_cache() == path   # stable across calls
