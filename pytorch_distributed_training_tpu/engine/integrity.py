"""Integrity sentinel: silent-data-corruption detection + quarantine.

Every fault the rest of the fault-tolerance layer survives is *loud* — a
raise, a hang, a dead peer, a truncated file.  Nothing upstream detects a
step that completes but computes the **wrong state**: at pod scale one
bit-flipped replica poisons every peer through the next allreduce (arXiv
1811.05233 §5 runs exactly this topology), and under ZeRO-1 weight-update
sharding (arXiv 2004.13336) a corrupted shard owner is the *sole
authority* for its optimizer slice.  This module closes that gap with the
same detect → classify → recover ladder the crash paths use:

- **Fingerprint** (:func:`fingerprint_state`): a per-leaf bitcast-uint32
  position-mixed wrapping-sum reduction over the full train state, folded
  FNV-style across leaves — one compiled scalar per check, cheap enough to
  run every ``check_interval`` steps.  Position mixing (index-dependent
  multiplier) makes the hash sensitive to *where* a bit flipped, not just
  the XOR of all words; bitcasting (not value casting) makes it sensitive
  to every representable bit including NaN payloads and -0.0.
- **Vote** (:meth:`IntegritySentinel.check`): fingerprints are compared
  across DP replicas and a strict majority identifies the diverged replica
  *by rank*.  ZeRO-aware: leaves whose sharding is not fully replicated
  hash their local shard, and those shard hashes are all-gathered with the
  replicated-state hash so the vote payload covers sharded optimizer state
  (shard hashes legitimately differ per rank, so in real multi-process
  mode the majority vote runs on the replicated-state hash and the
  gathered shard-hash vector rides along for attribution/diagnostics).
  With a single process the sentinel can *simulate* ``replicas`` voters —
  the injection/test path: every simulated peer reports the healthy hash
  unless ``sdc_flip`` armed a flip for its rank.
- **Classify + recover**: a diverged check restores the retained
  known-good snapshot (taken at the last passing check) and replays —
  a transient flip heals and the next check passes.  A replica that stays
  diverged for ``max_consecutive`` consecutive checks is *persistently*
  corrupt: the runner raises :class:`DivergedReplicaError`, which
  subclasses :class:`~.elastic.PeerLostError` so the existing quarantine
  machinery applies unchanged — emergency checkpoint from a healthy rank,
  peers detect the quarantined rank's exit through the elastic heartbeat
  layer, and the relaunch resumes reshaped without the bad host.
- **Checkpoint content integrity** (:func:`leaf_checksums`): a per-leaf
  CRC-32 manifest written next to every checkpoint by both save paths and
  verified on restore (engine/checkpoint.py) — a corrupt-but-well-formed
  checkpoint is rejected in favor of the newest *verified* earlier step,
  exactly like the truncated case.

Injection: ``sdc_flip@step[:rank]`` and ``ckpt_corrupt@step`` through the
``PDT_FAULT_SPEC`` grammar (engine/fault.py); the end-to-end proof is
``tests/test_integrity.py::test_runner_flip_recovery_end_to_end``.  All
``integrity_*`` counters flow through the telemetry registry like every
other recovery counter.
"""
from __future__ import annotations

import logging
import threading
import zlib
from collections import Counter
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import fault
from .elastic import PeerLostError
from ..telemetry.retrace import register_compiled

__all__ = [
    "DivergedReplicaError",
    "IntegritySentinel",
    "fingerprint_state",
    "leaf_checksums",
]

# Knuth multiplicative constant / golden-ratio word for the position mix,
# FNV-1 offset basis / prime for the cross-leaf fold — all uint32 wrapping.
_MIX_MULT = np.uint32(2654435761)
_MIX_XOR = np.uint32(0x9E3779B9)
_FNV_BASIS = np.uint32(0x811C9DC5)
_FNV_PRIME = np.uint32(0x01000193)


class DivergedReplicaError(PeerLostError):
    """A replica's state fingerprint stayed outside the healthy majority
    for ``max_consecutive`` checks: persistent corruption, quarantine it.

    Subclasses :class:`~.elastic.PeerLostError` on purpose — the recovery
    contract is the same as a dead peer's: this process exits with the
    diagnosis, surviving ranks observe its silence through the elastic
    heartbeat layer, and the relaunch resumes reshaped without the bad
    host (the emergency checkpoint, written by a *healthy* rank, carries
    the state across the reshape).

    Attributes:
      ranks: the persistently diverged replica ranks (== ``dead_ranks``).
      step: the iteration of the failing check.
    """

    def __init__(self, message: str, ranks=(), step: Optional[int] = None):
        super().__init__(message, dead_ranks=ranks, mid_step=False)
        self.ranks = tuple(ranks)
        self.step = step


# --------------------------------------------------------------- fingerprint
def _leaf_words(leaf) -> jnp.ndarray:
    """A leaf's raw bits as a flat uint32 vector (traceable).

    Bitcast — not value cast — wherever a same-width unsigned type exists,
    so every representable bit participates (NaN payloads, -0.0, denormals
    all hash differently).  Wider/odd dtypes degrade to a value cast: still
    deterministic, just coarser.
    """
    x = jnp.asarray(leaf)
    if x.dtype in (jnp.float32, jnp.int32, jnp.uint32):
        w = jax.lax.bitcast_convert_type(x, jnp.uint32)
    elif x.dtype in (jnp.bfloat16, jnp.float16):
        w = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    elif x.dtype in (jnp.int16, jnp.uint16, jnp.int8, jnp.uint8, jnp.bool_):
        w = x.astype(jnp.uint32)
    elif jnp.issubdtype(x.dtype, jnp.floating):
        w = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    else:  # wide ints (x64 off in this stack, but stay total)
        w = x.astype(jnp.uint32)
    return w.reshape(-1)


def _hash_leaves(leaves) -> jnp.ndarray:
    """Fold a sequence of array leaves into one uint32 (wrapping ops only:
    uint32 arithmetic wraps mod 2^32 in XLA, which is the point)."""
    total = jnp.uint32(_FNV_BASIS)
    for leaf in leaves:
        w = _leaf_words(leaf)
        pos = jnp.arange(w.shape[0], dtype=jnp.uint32)
        mixed = w * (pos * _MIX_MULT ^ _MIX_XOR)
        total = total * _FNV_PRIME ^ jnp.sum(mixed, dtype=jnp.uint32)
    return total


_hash_leaves_jit = register_compiled(
    "integrity/fingerprint", jax.jit(_hash_leaves)
)


def split_by_sharding(state) -> Tuple[List[Any], List[Any]]:
    """Partition ``state``'s leaves into (replicated, sharded) by their
    placement: a leaf whose sharding is not fully replicated contributes a
    *local-shard* hash (ZeRO-1 optimizer slices), everything else — plain
    DP state, host scalars — is replica-redundant and vote-checkable."""
    replicated, sharded = [], []
    for leaf in jax.tree_util.tree_leaves(state):
        sh = getattr(leaf, "sharding", None)
        if sh is not None and not getattr(sh, "is_fully_replicated", True):
            sharded.append(leaf)
        else:
            replicated.append(leaf)
    return replicated, sharded


def fingerprint_state(state) -> Tuple[int, int]:
    """(replicated_hash, local_shard_hash) of the full train state.

    The pair is what one replica reports into the vote: the first
    component must agree across healthy DP replicas; the second covers the
    leaves this process is the sole owner of (all-gathered by the caller
    so corruption there is at least attributable, per the module
    docstring).  Both are plain ints for JSON/compare friendliness.
    """
    replicated, sharded = split_by_sharding(state)
    repl = int(_hash_leaves_jit(tuple(replicated))) if replicated else int(_FNV_BASIS)
    shard = int(_hash_leaves_jit(tuple(sharded))) if sharded else int(_FNV_BASIS)
    return repl, shard


def _fold_pair(pair: Tuple[int, int]) -> int:
    return ((int(pair[0]) * int(_FNV_PRIME)) ^ int(pair[1])) & 0xFFFFFFFF


# ---------------------------------------------------------- checkpoint CRCs
def leaf_checksums(tree) -> Dict[str, int]:
    """Per-leaf CRC-32 manifest of ``tree`` (host or device arrays).

    Keys are stringified tree paths (``jax.tree_util.keystr``), values
    CRC-32 over dtype + shape + raw bytes — dtype/shape participate so a
    reinterpreted buffer of the right byte length still mismatches.  Used
    by the checkpoint layer on both save paths and on restore.
    """
    out: Dict[str, int] = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        arr = np.asarray(jax.device_get(leaf))
        crc = zlib.crc32(f"{arr.dtype}:{arr.shape}".encode())
        crc = zlib.crc32(np.ascontiguousarray(arr).tobytes(), crc)
        out[jax.tree_util.keystr(path)] = crc & 0xFFFFFFFF
    return out


def _flip_one_bit(state, logger: Optional[logging.Logger] = None):
    """Return ``state`` with one bit XOR-flipped in its first float param
    leaf (the injected SDC).  A low-order mantissa bit: numerically almost
    invisible — exactly the corruption only a bitwise fingerprint catches —
    and can never mint a NaN/Inf the anomaly guard would see first."""
    leaves, treedef = jax.tree_util.tree_flatten(state)
    idx = None
    for i, leaf in enumerate(leaves):
        if (
            hasattr(leaf, "dtype") and hasattr(leaf, "size") and leaf.size
            and jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)
        ):
            idx = i
            break
    if idx is None:
        raise RuntimeError("sdc_flip: state has no non-empty float leaf to flip")
    host = np.asarray(jax.device_get(leaves[idx]))
    buf = bytearray(host.tobytes())
    buf[0] ^= 0x01
    flipped = np.frombuffer(bytes(buf), dtype=host.dtype).reshape(host.shape)
    sharding = getattr(leaves[idx], "sharding", None)
    leaves = list(leaves)
    leaves[idx] = (
        jax.device_put(flipped, sharding) if sharding is not None else flipped
    )
    if logger is not None:
        logger.warning(
            "fault injection: sdc_flip — flipped 1 bit in state leaf %d "
            "(%s %s)", idx, host.dtype, host.shape,
        )
    return jax.tree_util.tree_unflatten(treedef, leaves)


# --------------------------------------------------------------- the sentinel
class IntegritySentinel:
    """Periodic fingerprint votes + retained-snapshot recovery.

    One instance per training process, consulted by the runner between
    steps (never inside the compiled step — the state is quiescent and
    owned there, so the read can't conflict with donated step buffers).

    ``replicas`` > ``process_count`` turns on *simulated* peers: the vote
    runs over ``replicas`` reports where every non-local rank reports the
    healthy fingerprint unless an ``sdc_flip`` was armed for it — the
    1-device test/bench path for attribution and classification.
    """

    def __init__(
        self,
        check_interval: int = 100,
        replicas: Optional[int] = None,
        rank: int = 0,
        process_count: int = 1,
        max_consecutive: int = 2,
        logger: Optional[logging.Logger] = None,
    ):
        if check_interval < 1:
            raise ValueError(
                f"integrity.check_interval must be >= 1, got {check_interval}"
            )
        if max_consecutive < 1:
            raise ValueError(
                f"integrity.max_consecutive must be >= 1, got {max_consecutive}"
            )
        self.check_interval = int(check_interval)
        self.replicas = int(replicas) if replicas is not None else int(process_count)
        if self.replicas < 1:
            raise ValueError(f"integrity.replicas must be >= 1, got {replicas}")
        self.rank = int(rank)
        self.process_count = int(process_count)
        self.simulated = self.replicas > self.process_count
        self.max_consecutive = int(max_consecutive)
        self._logger = logger or logging.getLogger(__name__)
        self._lock = threading.Lock()
        self._pending_flips: List[int] = []  # guarded by: self._lock
        self._consec: Counter = Counter()  # guarded by: self._lock
        self._snapshot: Optional[dict] = None  # guarded by: self._lock
        if self.replicas < 3:
            self._logger.info(
                "integrity sentinel: %d replica(s) — divergence is "
                "detectable but majority attribution needs >= 3 voters",
                self.replicas,
            )

    # ------------------------------------------------------------- schedule
    def due(self, step: int) -> bool:
        """Whether the check runs after step ``step`` completes."""
        return (step + 1) % self.check_interval == 0

    def arm_flip(self, rank: int) -> None:
        """Queue an injected bit flip for replica ``rank`` (< 0 = local),
        applied at the next check (``sdc_flip`` fault kind)."""
        with self._lock:
            self._pending_flips.append(int(rank))

    # ------------------------------------------------------------- snapshot
    def retain(self, state, step: int, position: Optional[dict] = None) -> None:
        """Keep a host copy of ``state`` as the known-good recovery point
        (the state *after* step ``step``), plus its fingerprint and the
        input-pipeline position a replay must restart from."""
        snap = {
            "state": jax.device_get(state),
            "step": int(step),
            "fingerprint": fingerprint_state(state),
            "position": dict(position) if position else None,
        }
        with self._lock:
            self._snapshot = snap

    def rebase(self, state, step: int, position: Optional[dict] = None) -> None:
        """Re-anchor the sentinel on a state restored from OUTSIDE it
        (anomaly rollback, checkpoint resume): retain the restored state as
        the new recovery point AND clear the per-replica consecutive
        divergence streaks — they were measured against a timeline the
        caller just abandoned, so carrying them forward would escalate the
        first post-restore divergence straight to quarantine."""
        self.retain(state, step, position)
        with self._lock:
            self._consec.clear()

    @property
    def snapshot_step(self) -> Optional[int]:
        with self._lock:
            return None if self._snapshot is None else self._snapshot["step"]

    def restore_snapshot(self, state) -> Tuple[Any, int, Optional[dict], bool]:
        """Re-place the retained snapshot onto ``state``'s shardings.

        Returns ``(restored_state, snapshot_step, position, verified)``;
        ``verified`` is False when the restored state's fingerprint does
        not reproduce the retained one — the corruption survived the
        restore (bad host memory, not a transient flip), so the caller
        must escalate to quarantine instead of looping restore→diverge.
        """
        with self._lock:
            snap = self._snapshot
        if snap is None:
            raise RuntimeError("integrity: no retained snapshot to restore")

        def _place(cur, host):
            sh = getattr(cur, "sharding", None)
            return jax.device_put(host, sh) if sh is not None else host

        restored = jax.tree_util.tree_map(_place, state, snap["state"])
        ok = fingerprint_state(restored) == tuple(snap["fingerprint"])
        return restored, snap["step"], snap["position"], ok

    # ----------------------------------------------------------------- vote
    def _gather_reports(self, local_pair: Tuple[int, int],
                        healthy_pair: Tuple[int, int],
                        remote_flips: List[int]) -> List[int]:
        """One folded uint32 report per replica rank."""
        if self.simulated or self.process_count == 1:
            reports = []
            for r in range(self.replicas):
                if r == self.rank:
                    reports.append(_fold_pair(local_pair))
                elif r in remote_flips:
                    # a simulated peer whose state flipped: any report
                    # outside the healthy consensus — derived, not random,
                    # so reruns are deterministic
                    reports.append(_fold_pair(healthy_pair) ^ 0x5A5A5A5A)
                    fault.bump("injected_sdc_flips")
                else:
                    reports.append(_fold_pair(healthy_pair))
            return reports
        # Real multi-process mode: all-gather (replicated_hash, shard_hash)
        # pairs.  The vote runs on the replicated-state hash — shard hashes
        # differ per rank by construction, so they ride along for
        # attribution/diagnostics rather than voting (module docstring).
        from jax.experimental import multihost_utils

        gathered = multihost_utils.process_allgather(
            np.asarray(local_pair, dtype=np.uint32)
        )
        return [int(pair[0]) for pair in np.asarray(gathered).reshape(-1, 2)]

    def check(self, state, step: int) -> Tuple[Any, Dict[str, Any]]:
        """Run one fingerprint vote after step ``step``.

        Returns ``(state, verdict)`` — the state comes back because an
        armed *local* ``sdc_flip`` really corrupts it (the returned tree is
        the corrupted one the runner must adopt; detection would be
        fiction otherwise).  Verdict keys: ``diverged`` (ranks outside the
        majority), ``persistent`` (diverged for >= max_consecutive checks),
        ``local_diverged``, ``majority`` (the winning report or None when
        no strict majority exists), ``reports``.
        """
        with self._lock:
            pending, self._pending_flips = self._pending_flips, []
        local_flip = any(r < 0 or r == self.rank for r in pending)
        remote_flips = [r for r in pending if 0 <= r != self.rank]
        healthy_pair = fingerprint_state(state)
        local_pair = healthy_pair
        if local_flip:
            state = _flip_one_bit(state, self._logger)
            fault.bump("injected_sdc_flips")
            local_pair = fingerprint_state(state)
        reports = self._gather_reports(local_pair, healthy_pair, remote_flips)
        fault.bump("integrity_checks")
        if self.replicas > 1:
            fault.bump("integrity_votes")
        modal, modal_n = Counter(reports).most_common(1)[0]
        has_majority = modal_n * 2 > len(reports)
        diverged = [r for r, rep in enumerate(reports) if rep != modal]
        if diverged:
            fault.bump("integrity_divergences")
        with self._lock:
            for r in range(len(reports)):
                if r in diverged:
                    self._consec[r] += 1
                else:
                    self._consec[r] = 0
            persistent = sorted(
                r for r in diverged if self._consec[r] >= self.max_consecutive
            )
        if diverged:
            self._logger.error(
                "integrity check at step %d: replica(s) %s diverged from "
                "the %s of %d voters (reports %s)%s",
                step, diverged,
                "majority" if has_majority else "LARGEST MINORITY (no "
                "strict majority — attribution unreliable)",
                len(reports), [f"{r:08x}" for r in reports],
                f"; persistent: {persistent}" if persistent else "",
            )
        return state, {
            "step": step,
            "diverged": diverged,
            "persistent": persistent,
            "local_diverged": self.rank in diverged,
            "majority": modal if has_majority else None,
            "reports": reports,
        }
