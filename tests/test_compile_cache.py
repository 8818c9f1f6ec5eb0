"""Where the entry points keep JAX's persistent compile cache."""
import jax
import pytest

from repro.compile_cache import CHECKOUT_ROOT, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env", [None, "placed"])
def test_cache_dir_honours_env_else_checkout(env, tmp_path, monkeypatch,
                                             restore_cache_dir):
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(CHECKOUT_ROOT / ".jax_cache")
    else:
        want = str(tmp_path / env)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert (CHECKOUT_ROOT / "chip_smoke.py").exists()
