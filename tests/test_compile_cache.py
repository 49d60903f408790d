"""Where the persistent XLA compile cache lives: where the operator put it
(JAX_COMPILATION_CACHE_DIR — jax reads the variable itself, the code sets no
directory), else ONE fixed path inside the checkout."""

import os

from tidb_tpu.ops import dag_kernel

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _config_updates(monkeypatch, env_dir):
    import jax

    calls = {}
    monkeypatch.setattr(jax.config, "update", lambda k, v: calls.__setitem__(k, v))
    monkeypatch.setattr(dag_kernel._ensure_x64, "_cc_done", False, raising=False)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    dag_kernel._ensure_x64()
    return calls


def test_variable_set_code_sets_no_directory(monkeypatch):
    calls = _config_updates(monkeypatch, "/some/dir")
    assert "jax_compilation_cache_dir" not in calls
    assert calls["jax_enable_x64"] is True


def test_variable_unset_one_fixed_path_inside_the_checkout(monkeypatch):
    calls = _config_updates(monkeypatch, None)
    d = calls["jax_compilation_cache_dir"]
    assert d == dag_kernel.COMPILE_CACHE_DIR == os.path.join(REPO, "tidb_tpu", "_xla_cache")
    # listed in .gitignore: the cache is made at run time, never committed
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert "tidb_tpu/_xla_cache/" in f.read().split()
