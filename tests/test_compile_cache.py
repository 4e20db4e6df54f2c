"""One compile-cache directory for every entry point (utils/cache.py):
``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it; nothing is set in
code), else ``<checkout>/.jax_cache``."""

import os
import sys

import jax
import pytest

from spark_examples_tpu.utils import cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_KEYS = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def cache_updates(monkeypatch):
    """Record (and do not apply) compile-cache config updates; every other
    config update passes through."""
    updates = {}
    real_update = jax.config.update

    def update(name, value):
        if name in CACHE_KEYS:
            updates[name] = value
        else:
            real_update(name, value)

    monkeypatch.setattr(jax.config, "update", update)
    monkeypatch.delenv("SPARK_EXAMPLES_TPU_NO_CACHE", raising=False)
    return updates


def test_env_var_is_used_and_not_overridden(cache_updates, monkeypatch, tmp_path):
    monkeypatch.setenv(cache.CACHE_DIR_ENV, str(tmp_path))
    assert cache.compile_cache_dir() == str(tmp_path)
    cache.enable_persistent_compile_cache()
    assert "jax_compilation_cache_dir" not in cache_updates


def test_unset_env_defaults_to_checkout(cache_updates, monkeypatch):
    monkeypatch.delenv(cache.CACHE_DIR_ENV, raising=False)
    expected = os.path.join(REPO, ".jax_cache")
    assert cache.compile_cache_dir() == expected
    cache.enable_persistent_compile_cache()
    assert cache_updates["jax_compilation_cache_dir"] == expected


def test_entries_count_the_resolved_dir(monkeypatch, tmp_path):
    monkeypatch.setenv(cache.CACHE_DIR_ENV, str(tmp_path / "missing"))
    assert cache.compile_cache_entries() == 0
    monkeypatch.setenv(cache.CACHE_DIR_ENV, str(tmp_path))
    (tmp_path / "a").write_text("x")
    (tmp_path / "b").write_text("y")
    assert cache.compile_cache_entries() == 2


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "default"])
def test_daemon_and_bench_use_the_same_dir(cache_updates, monkeypatch, tmp_path, env_set):
    """The daemon and bench.py resolve the directory through the same
    function: never the daemon's run dir."""
    if env_set:
        monkeypatch.setenv(cache.CACHE_DIR_ENV, str(tmp_path / "shared"))
    else:
        monkeypatch.delenv(cache.CACHE_DIR_ENV, raising=False)
    expected = None if env_set else cache.compile_cache_dir()

    from spark_examples_tpu.serve.daemon import PcaService

    run_dir = tmp_path / "serve"
    service = PcaService(run_dir=str(run_dir), persistent_cache=True).start()
    try:
        daemon_dir = cache_updates.pop("jax_compilation_cache_dir", None)
        assert cache_updates.pop("jax_persistent_cache_min_compile_time_secs") == 0.0
    finally:
        assert service.stop(timeout=60)
        cache.reset_compile_cache_stats()  # detach the run dir's ledger
    assert daemon_dir == expected

    import bench

    monkeypatch.setattr(bench, "_run_config", lambda name, device: {"stub": name})
    monkeypatch.setattr(sys, "argv", ["bench.py", "--config", "brca1"])
    bench.main()
    assert cache_updates.get("jax_compilation_cache_dir") == expected
