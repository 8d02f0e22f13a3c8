"""Determinism + iteration helpers.

Re-provides the ``dl_lib.utils`` surface pinned by the reference at
train_distributed.py:27 (``make_deterministic``, ``make_iter_dataloader``),
re-designed for a JAX runtime: JAX PRNG keys are explicit, so
``make_deterministic`` seeds the *host* RNGs (python/numpy/torch-if-present)
and records a global base seed from which the framework derives
``jax.random.PRNGKey`` streams.
"""
from __future__ import annotations

import os
import random
from typing import Generator, Iterable, Optional, Tuple

import numpy as np

__all__ = [
    "make_deterministic",
    "get_base_seed",
    "make_iter_dataloader",
    "enable_compile_cache",
]

_BASE_SEED: Optional[int] = None
# the directory that holds the package: relative cache paths anchor here
_CHECKOUT_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def make_deterministic(seed: int) -> None:
    """Seed all host-side RNGs and record the framework base seed.

    Reference contract (train_distributed.py:51-53, :141-142): called once in
    the parent and once per worker with the *same* seed on all ranks, so model
    init is identical everywhere (which is what makes DDP's initial param
    broadcast redundant — we rely on the same property: replicated same-seed
    init instead of a broadcast collective).

    On TPU/XLA, kernel determinism is the default; there is no
    ``cudnn.deterministic`` analog to set.
    """
    global _BASE_SEED
    _BASE_SEED = int(seed)
    random.seed(seed)
    np.random.seed(seed % (2**32))
    try:  # torch is an optional host-side dependency (parity tests only)
        import torch

        torch.manual_seed(seed)
    except ImportError:  # pragma: no cover
        pass


def get_base_seed(default: int = 0) -> int:
    """Base seed recorded by :func:`make_deterministic` (``default`` if unset)."""
    return _BASE_SEED if _BASE_SEED is not None else default


def enable_compile_cache(directory: Optional[str] = None) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    The TPU-native analog of the reference's ``cudnn.benchmark = True``
    (train_distributed.py:54, SURVEY.md §2.3 autotune row): cuDNN autotune
    amortizes kernel selection across runs; XLA's persistent cache amortizes
    whole-program compilation across *launches*.

    One rule for every entry point (the Runner's ``training.compile_cache``,
    ``python -m …serving``, ``chip_smoke.py``):

    - ``JAX_COMPILATION_CACHE_DIR`` set: the cache is there.  JAX reads the
      variable itself, so nothing here writes ``jax_compilation_cache_dir``
      and ``directory`` is ignored — whoever launches the process decides
      where compiled programs persist.
    - not set: ``directory`` (default ``.xla_cache``), a relative path
      resolved against the checkout root.  The directory is where the next
      launch looks, so it must not follow the working directory.

    Thresholds are zeroed so every executable is cached regardless of compile
    time or size (the default 1s floor would skip the small eval and
    serving-bucket programs a relaunch needs just as much).
    """
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        directory = env_dir
    else:
        directory = os.path.expanduser(directory or ".xla_cache")
        if not os.path.isabs(directory):
            directory = os.path.join(_CHECKOUT_ROOT, directory)
        os.makedirs(directory, exist_ok=True)
        if jax.config.jax_compilation_cache_dir != directory:
            from jax.experimental.compilation_cache import compilation_cache

            # the cache object is initialized lazily ONCE per process; a
            # dir set after first use is silently ignored without a reset
            compilation_cache.reset_cache()
            jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # -1 disables the size floor; 0 would mean "filesystem-dependent default",
    # which can silently reinstate a 64KB floor on some backends
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory


def make_iter_dataloader(
    loader: Iterable,
    start_iter: int = 0,
    start_epoch: Optional[int] = None,
    skip_batches: Optional[int] = None,
) -> Generator[Tuple, None, None]:
    """Convert an epoch-based loader into an infinite per-iteration generator.

    Reference contract (train_distributed.py:27, :249-252): the training loop
    is iteration-based (``train_iters`` total) and draws ``(img, label)``
    batches forever.  Between epochs we advance the loader's epoch so the
    distributed shuffle re-randomizes (the analog of
    ``DistributedSampler.set_epoch``).

    ``start_iter`` fast-forwards the stream to a checkpointed position
    (epoch = start_iter // batches_per_epoch, then skip the remainder at the
    index level) so a resumed run sees exactly the batch *indices* a straight
    run would.  For index-seeded datasets (synthetic) this makes resume
    bit-exact; for datasets with stochastic augmentation driven by the global
    host RNG (ImageFolder crop/flip) the skipped decodes don't consume RNG
    draws, so augmented pixels after resume differ from a hypothetical
    uninterrupted run — sample identity and visit order are still exact.

    ``start_epoch``/``skip_batches`` (both or neither) OVERRIDE that
    derivation with an explicitly persisted pipeline position (the elastic
    checkpoint sidecar, engine/checkpoint.py): after a mesh reshape the
    batch count per epoch may differ from the saving topology's, so
    dividing the step counter by the *current* epoch length would land on
    the wrong sample — the recorded (epoch, batches-consumed) pair is
    topology-independent under ``batch_division: world``.

    Validation runs eagerly at the CALL (this is a wrapper around the
    actual generator), so a bad resume position fails where it was
    computed, not at the loop's first ``next()``.
    """
    if hasattr(loader, "__len__") and len(loader) == 0:
        # drop_last can leave zero full batches (dataset shard < batch size);
        # the infinite loop below would busy-spin forever on an empty loader
        raise ValueError(
            "loader yields no batches (dataset shard smaller than batch size "
            "with drop_last?) — the iteration-based loop would spin forever"
        )
    if (start_epoch is None) != (skip_batches is None):
        raise ValueError(
            "start_epoch and skip_batches must be given together "
            f"(got start_epoch={start_epoch}, skip_batches={skip_batches})"
        )
    epoch = 0
    if start_epoch is not None:
        epoch = int(start_epoch)
        skip = int(skip_batches)
        if epoch < 0 or skip < 0:
            raise ValueError(
                f"start_epoch/skip_batches must be >= 0, got "
                f"{start_epoch}/{skip_batches}"
            )
        if skip and hasattr(loader, "skip_next"):
            loader.skip_next(skip)
    elif start_iter:
        batches_per_epoch = len(loader)
        epoch = start_iter // batches_per_epoch
        skip = start_iter % batches_per_epoch
        if skip and hasattr(loader, "skip_next"):
            loader.skip_next(skip)

    def _stream(epoch):
        while True:
            if hasattr(loader, "set_epoch"):
                loader.set_epoch(epoch)
            for batch in loader:
                yield batch
            epoch += 1

    return _stream(epoch)
