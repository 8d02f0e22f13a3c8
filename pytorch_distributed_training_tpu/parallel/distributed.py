"""Multi-host bootstrap.

Replaces ``dist.init_process_group(backend, init_method, world_size, rank)``
(reference: train_distributed.py:149-154) with the JAX coordination service:
the reference's TCPStore rendezvous URL (``--dist-url tcp://host:port``,
:42) maps directly onto the coordinator address of
``jax.distributed.initialize``; ``--num-nodes``/``--rank`` map onto
``num_processes``/``process_id`` (SURVEY.md §5.8).

Backend-name mapping: the reference defaults ``--dist-backend nccl``; the
TPU runtime accepts ``tpu`` / ``xla`` (and treats ``nccl`` as a compat alias
with a warning, so reference launch scripts keep working unmodified).  The
name selects nothing: JAX runs on the platform it finds, and the log line
below says which one that is.
"""
from __future__ import annotations

import logging
from typing import Optional, Tuple
from urllib.parse import urlparse

import jax

__all__ = ["parse_dist_url", "initialize_distributed"]

_ACCEPTED_BACKENDS = {"tpu", "xla", "nccl", "gloo"}


def parse_dist_url(dist_url: str) -> Tuple[str, int]:
    """``tcp://host:port`` -> ``(host, port)`` (reference URL scheme, :42)."""
    parsed = urlparse(dist_url)
    if parsed.scheme not in ("tcp", ""):
        raise ValueError(f"unsupported dist-url scheme: {dist_url!r}")
    host = parsed.hostname or "127.0.0.1"
    if parsed.port is None:
        raise ValueError(f"dist-url must include a port: {dist_url!r}")
    return host, parsed.port


def initialize_distributed(
    dist_url: str,
    num_nodes: int,
    rank: int,
    backend: str = "tpu",
    logger: Optional[logging.Logger] = None,
) -> None:
    """Bring up the multi-host runtime (one controller process per host).

    No-op for single-host runs — ``jax.devices()`` already spans the local
    chips, and in-process SPMD needs no coordinator.  The reference's
    per-GPU ``mp.spawn`` topology (:116-135) is deliberately not replicated
    (SURVEY.md §7 deviations): its ``--multiprocessing`` flag becomes a
    compat no-op at the CLI layer.
    """
    log = logger or logging.getLogger(__name__)
    backend = (backend or "tpu").lower()
    if backend not in _ACCEPTED_BACKENDS:
        raise ValueError(
            f"unknown --dist-backend {backend!r} (accepted: {sorted(_ACCEPTED_BACKENDS)})"
        )
    if backend in ("nccl", "gloo"):
        log.warning(
            "--dist-backend %s is a GPU-era alias; using the XLA/TPU runtime", backend
        )
    if num_nodes is None or num_nodes <= 1:
        return
    host, port = parse_dist_url(dist_url)
    jax.distributed.initialize(
        coordinator_address=f"{host}:{port}",
        num_processes=num_nodes,
        process_id=rank,
    )
    device = jax.devices()[0]
    log.info(
        "jax.distributed initialized: process %d/%d, %d global %s devices (%s)",
        rank,
        num_nodes,
        jax.device_count(),
        device.platform,
        device.device_kind,
    )
