"""Host-staged KV-block transfer protocol for disaggregated serving.

The wire format between a prefill replica's paged pool and a decode
replica's (serving/disagg.py): each transferred unit is ONE physical
block — ``block_size`` token rows gathered from every per-row pool leaf
— addressed by the pool's content-chained prefix key (kv_pool.py
``_chain_keys``) and sealed with a per-block CRC-32 over the raw bytes,
the same checksum scheme the checkpoint manifest uses for corruption
detection (engine/integrity.py ``leaf_checksums``).  Content addressing
is what makes the transfer safe to dedupe and replay: equal keys imply
bitwise-equal K/V (prefill with identical config/params/bucket is a
deterministic jit program), so an imported block is interchangeable
with a locally-recomputed one and token parity holds by construction.

Host-staged on purpose: blocks round-trip through ``numpy`` arrays
(device → host gather on export, host → device scatter on import)
because the single-process fleet has no device-to-device fabric to
model — what that staging costs on a chip has not been measured.

This module is pure data plumbing — no locks, no threads, no pool
mutation beyond the functional ``.at[].set`` scatter.  The scheduler
owns WHEN extraction/scattering happen (on its loop thread, at tick
boundaries); serving/disagg.py owns the recovery ladder around failed
or corrupt transfers.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..ops.attention import pool_leaf_role

__all__ = [
    "BlockPayload",
    "BlockRef",
    "corrupt_payload",
    "extract_block_refs",
    "extract_payloads",
    "materialize_payloads",
    "payload_checksum",
    "pool_row_leaves",
    "scatter_payloads",
    "verify_payload",
]


def _path_name(path) -> str:
    return "/".join(
        str(getattr(part, "key", getattr(part, "name", ""))) for part in path
    )


def pool_row_leaves(pool, n_rows: int) -> List[Tuple[str, Any]]:
    """``(name, leaf)`` for every per-row leaf of the paged pool, sorted by
    name: a K/V pair's two leaves or a latent cache's one, a layer.

    Found by what the attention modules declare
    (:func:`..ops.attention.pool_leaf_role`: the variable they made, with
    the pool's rows in front), the same way the chaos SDC injector finds
    its corruption targets (scheduler ``_corrupt_pool_rows``).  Sorted
    order makes the leaf set deterministic on both ends of a transfer,
    which the chained checksum relies on.
    """
    flat = jax.tree_util.tree_flatten_with_path(pool)[0]
    out = [
        (_path_name(path), leaf) for path, leaf in flat
        if pool_leaf_role(path, leaf, n_rows)
    ]
    out.sort(key=lambda kv: kv[0])
    return out


def payload_checksum(key: tuple, index: int, arrays: Dict[str, np.ndarray]) -> int:
    """CRC-32 chained over the block's identity and every leaf's bytes.

    The identity (chain key + block index) is part of the digest so a
    payload cannot be silently replayed under a different address; each
    leaf contributes a ``name:dtype:shape`` header before its raw bytes
    (the integrity-manifest idiom) so truncation or a reshaped array
    fails the check, not just flipped bits.
    """
    crc = zlib.crc32(repr((key, index)).encode())
    for name in sorted(arrays):
        arr = arrays[name]
        crc = zlib.crc32(f"{name}:{arr.dtype}:{arr.shape}".encode(), crc)
        crc = zlib.crc32(np.ascontiguousarray(arr).tobytes(), crc)
    return crc & 0xFFFFFFFF


@dataclass
class BlockPayload:
    """One physical block in flight: ``block_size`` rows of every pool
    leaf, keyed by the content-chained prefix address, CRC-sealed."""

    key: tuple
    index: int  # position of this block in the prefix chain, 0-based
    arrays: Dict[str, np.ndarray]  # leaf name -> [block_size, ...] rows
    crc: int

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self.arrays.values())


@dataclass
class BlockRef:
    """One block SELECTED for transfer but not yet host-staged: lazy
    per-leaf device slices instead of materialized numpy rows.

    The split exists so the scheduler thread only pays the cheap device
    slice dispatch (``leaf[rows]`` — an async device gather, no host
    sync) and the expensive part — device→host copies plus the CRC seal
    — runs on a staging executor (serving/disagg.py).  Safety: the
    slices are taken at a tick boundary while the pool is quiescent, and
    each is a NEW buffer, not a view of its leaf, so the snapshot stays
    valid after later ticks donate the pool to the decode programs and
    its leaves are updated in place.  A ref never holds a leaf itself.
    """

    key: tuple
    index: int  # position of this block in the prefix chain, 0-based
    slices: Dict[str, Any]  # leaf name -> [block_size, ...] device rows


def extract_block_refs(
    kv, pool, prompt: Sequence[int], namespace=None
) -> List[BlockRef]:
    """Select the longest cached chain for ``prompt`` as lazy refs.

    Runs on the source scheduler's loop thread (single-thread pool
    confinement) but does NOT block on any host copy.  Cached blocks are
    fully written by construction — registration is capped at
    ``(prompt_len - 1) // block_size`` FULL blocks.
    """
    chain = kv.cached_chain(prompt, namespace)
    if not chain:
        return []
    bs = kv.block_size
    leaves = pool_row_leaves(pool, kv.num_blocks * bs)
    return [
        BlockRef(
            key=key,
            index=index,
            slices={
                name: leaf[blk * bs : (blk + 1) * bs] for name, leaf in leaves
            },
        )
        for index, (key, blk) in enumerate(chain)
    ]


def materialize_payloads(
    refs: Sequence[BlockRef], chunk_rows: Optional[int] = None
) -> List[BlockPayload]:
    """Host-stage refs into CRC-sealed payloads (any thread).

    This is the expensive half of an export — the device→host copies and
    the checksum over every byte.  ``chunk_rows`` bounds each individual
    ``np.asarray`` to that many leading rows (None = whole leaf slice in
    one copy): with several transfers sharing one bounded staging
    executor, chunking keeps any single copy from monopolizing a worker
    and caps the transient host buffer per copy.
    """
    if chunk_rows is not None and chunk_rows < 1:
        raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")
    out: List[BlockPayload] = []
    for ref in refs:
        arrays: Dict[str, np.ndarray] = {}
        for name, sl in ref.slices.items():
            n = sl.shape[0]
            if chunk_rows is None or chunk_rows >= n:
                arrays[name] = np.asarray(sl)
            else:
                arrays[name] = np.concatenate(
                    [
                        np.asarray(sl[i : i + chunk_rows])
                        for i in range(0, n, chunk_rows)
                    ]
                )
        out.append(
            BlockPayload(
                key=ref.key,
                index=ref.index,
                arrays=arrays,
                crc=payload_checksum(ref.key, ref.index, arrays),
            )
        )
    return out


def extract_payloads(
    kv, pool, prompt: Sequence[int], namespace=None
) -> List[BlockPayload]:
    """Gather the longest cached chain for ``prompt`` into payloads.

    The synchronous composition of :func:`extract_block_refs` and
    :func:`materialize_payloads` — the staging cost lands on the calling
    thread.  The disaggregated transfer path splits the two phases
    instead (refs on the scheduler thread, staging on the coordinator's
    executor); this stays for callers that want a one-shot export.
    """
    return materialize_payloads(
        extract_block_refs(kv, pool, prompt, namespace=namespace)
    )


def verify_payload(payload: BlockPayload) -> bool:
    """Recompute the CRC over what actually arrived."""
    return (
        payload_checksum(payload.key, payload.index, payload.arrays)
        == payload.crc
    )


def corrupt_payload(payload: BlockPayload) -> None:
    """Flip one byte of the first leaf AFTER sealing (fault-injection
    hook for ``kv_transfer_corrupt``): the stale CRC must now reject.
    Dtype-agnostic via a bytes round-trip — bf16 has no numpy view."""
    name = sorted(payload.arrays)[0]
    arr = payload.arrays[name]
    raw = bytearray(arr.tobytes())
    raw[0] ^= 0xFF
    payload.arrays[name] = np.frombuffer(
        bytes(raw), dtype=arr.dtype
    ).reshape(arr.shape)


def scatter_payloads(pool, n_rows: int, accepted: List[Tuple[int, BlockPayload]]):
    """Write accepted payloads into their adopted blocks, one scatter
    per leaf (batched ``.at[rows].set``), returning the updated pool.

    ``accepted`` pairs each payload with the LOCAL block id the
    importing pool adopted for it — physical ids are replica-private;
    only the content keys travel.
    """
    if not accepted:
        return pool
    names = sorted(accepted[0][1].arrays)
    rows_parts: List[np.ndarray] = []
    vals: Dict[str, List[np.ndarray]] = {name: [] for name in names}
    for blk, payload in accepted:
        bsz = payload.arrays[names[0]].shape[0]
        rows_parts.append(np.arange(blk * bsz, (blk + 1) * bsz))
        for name in names:
            vals[name].append(payload.arrays[name])
    rows = np.concatenate(rows_parts)
    stacked = {name: np.concatenate(vals[name]) for name in names}

    def _write(path, leaf):
        name = _path_name(path)
        if name in stacked and hasattr(leaf, "shape") and leaf.shape[:1] == (
            n_rows,
        ):
            return leaf.at[rows].set(stacked[name].astype(leaf.dtype))
        return leaf

    return jax.tree_util.tree_map_with_path(_write, pool)
