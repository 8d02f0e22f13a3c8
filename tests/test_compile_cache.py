"""Persistent XLA compilation cache (training.compile_cache).

The TPU-native analog of the reference's ``cudnn.benchmark = True``
(train_distributed.py:54; SURVEY.md §2.3 "cuDNN autotune" row): amortize
program compilation across launches via JAX's persistent cache.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from pytorch_distributed_training_tpu.utils import enable_compile_cache


@pytest.fixture
def _restore_cache_config():
    saved = {
        name: getattr(jax.config, name)
        for name in (
            "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
        )
    }
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()  # drop the initialized cache object too


def test_enable_compile_cache_writes_entries(
    tmp_path, _restore_cache_config, monkeypatch
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cache_dir = tmp_path / "xla-cache"
    returned = enable_compile_cache(str(cache_dir))
    assert returned == str(cache_dir)
    assert cache_dir.is_dir()

    # A program this process has never compiled: its executable must land in
    # the cache directory (thresholds are zeroed by enable_compile_cache, so
    # even a trivial compile is persisted).
    @jax.jit
    def f(x):
        return jnp.sin(x) * 41.25 + jnp.cos(x) ** 3

    f(jnp.arange(7.0)).block_until_ready()
    entries = list(cache_dir.iterdir())
    assert entries, "no cache entries written"


def test_env_variable_places_the_cache(tmp_path, _restore_cache_config, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: the cache is where the launcher put it.
    The helper leaves ``jax_compilation_cache_dir`` alone (JAX reads the
    variable itself at import) and only zeroes the two thresholds."""
    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(str(tmp_path / "ignored")) == placed
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "ignored").exists()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_default_and_relative_paths_anchor_at_the_checkout(
    tmp_path, _restore_cache_config, monkeypatch
):
    """Variable not set: ``<checkout>/.xla_cache`` by default, and a relative
    ``training.compile_cache`` lands under the checkout — never under the
    working directory, which a relaunch may not share."""
    from pytorch_distributed_training_tpu import utils

    # the real anchor is the directory that holds the package and tests/
    assert utils._CHECKOUT_ROOT == os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    checkout = tmp_path / "checkout"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(utils, "_CHECKOUT_ROOT", str(checkout))
    monkeypatch.chdir(tmp_path)  # somewhere else than the checkout
    assert enable_compile_cache() == str(checkout / ".xla_cache")
    assert jax.config.jax_compilation_cache_dir == str(checkout / ".xla_cache")
    assert enable_compile_cache("run/xla-cache") == str(checkout / "run/xla-cache")
    assert (checkout / "run/xla-cache").is_dir()
    assert not (tmp_path / "run").exists()


def test_runner_config_key_wires_cache(tmp_path, _restore_cache_config, monkeypatch):
    """training.compile_cache: the Runner enables the cache before building
    its compiled steps, so a config-driven run populates the directory."""
    from pytorch_distributed_training_tpu.engine import Runner

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

    cache_dir = tmp_path / "run-cache"
    cfg = {
        "dataset": {
            "name": "synthetic",
            "root": str(tmp_path),
            "n_classes": 4,
            "image_size": 32,
            "n_samples": 64,
        },
        "training": {
            "optimizer": {
                "name": "SGD", "lr": 0.05, "weight_decay": 1.0e-4, "momentum": 0.9,
            },
            "lr_schedule": {"name": "multi_step", "milestones": [4], "gamma": 0.1},
            "train_iters": 2,
            "print_interval": 1,
            "val_interval": 2,
            "batch_size": 16,
            "num_workers": 2,
            "sync_bn": False,
            "compile_cache": str(cache_dir),
        },
        "validation": {"batch_size": 16, "num_workers": 2},
        "model": {"name": "ResNet18"},
    }
    runner = Runner(
        num_nodes=1,
        rank=0,
        seed=7,
        dist_url="tcp://127.0.0.1:9907",
        dist_backend="tpu",
        multiprocessing=False,
        logger_queue=None,
        global_cfg=cfg,
        tb_writer_constructor=lambda: None,
    )
    runner()
    assert runner.iter == 2
    assert cache_dir.is_dir()
    assert any(cache_dir.iterdir()), "Runner did not populate the compile cache"
    assert jax.config.jax_compilation_cache_dir == str(cache_dir)
