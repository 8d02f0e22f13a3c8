"""Deterministic fault injection + recovery counters (the chaos harness).

Every recovery path in the fault-tolerance layer (anomaly-step guard,
retrying checkpoint I/O, worker respawn, hung-step watchdog) is proved by
injecting its failure deterministically and asserting the recovery — not by
hoping production reproduces it.  The injector is a process-global registry
parsed from the ``PDT_FAULT_SPEC`` environment variable (or the
``training.fault_tolerance.fault_spec`` config key; env wins so a chaos
wrapper can override any config).

Spec grammar — a list of entries separated by ``;`` or ``,`` (both
accepted so shell-quoted comma lists like
``PDT_FAULT_SPEC="kill_peer@8,sdc_flip@9:0"`` compose multiple concurrent
faults), each entry ``kind@step[:arg]``.  The whole list is validated at
parse time: any malformed entry, unknown kind, or duplicate ``kind@step``
pair rejects the entire spec — a chaos scenario must fail loudly at
install, never silently drop one of its faults:

    nan_batch@K        poison the training batch fed to step K with NaNs
                       (float image pipelines; the anomaly guard must skip
                       the step)
    kill_worker@K[:W]  SIGKILL loader pool worker W (default 0) at step K
                       (the pool must respawn it, no batch lost)
    stall_step@K[:SEC] sleep SEC (default 1.0) inside step K's host window
                       (the watchdog must fire)
    kill_peer@K[:R]    SIGKILL THIS training process at step K when its
                       process index is R (default -1 = any rank) — the
                       multi-host peer-death scenario: surviving ranks must
                       detect the silence via the elastic heartbeat layer
                       (engine/elastic.py) instead of hanging in the next
                       collective
    sdc_flip@K[:R]     silently flip one parameter bit on replica R
                       (default 0; -1 = whichever rank parses it) at step K
                       — no raise, no NaN: the integrity sentinel
                       (engine/integrity.py) must detect the divergence at
                       its next fingerprint vote, attribute it to rank R,
                       and restore the healthy-majority state
    ckpt_corrupt@K     flip one bit in the payload of the checkpoint SAVED
                       at step K (after its checksum manifest is computed)
                       — the save commits cleanly and orbax restores it
                       without error; only the manifest verification at
                       restore time can reject it in favor of the newest
                       verified earlier step
    ckpt_fail@A[:N]    fail checkpoint-save attempts A..A+N-1 (0-based
                       attempt ordinal across the process; the retry policy
                       must absorb them)
    restore_fail@A[:N] same for checkpoint-restore attempts
    ckpt_async_fail@A[:N]
                       same for ASYNC checkpoint-write attempts — fires on
                       the background writer thread (the ``ckpt_async_write``
                       fail point), so the chaos harness can kill an
                       in-flight overlapped save deterministically and prove
                       the deferred-error + restore-fallback contract

Serving-side kinds (the ``step`` is the continuous scheduler's TICK
index, 1-based — serving/scheduler.py consults the injector once per
tick):

    serve_nan@T[:S]    corrupt the KV-pool rows of the request occupying
                       slot S (default 0) at tick T with NaNs — the
                       on-device output guard must evict exactly that
                       request, bit-exact for every other slot
    serve_raise@T[:S]  the request in slot S (default 0) raises from the
                       decode dispatch at tick T — the poison-bisect path
                       must isolate it without failing the world
    serve_device_lost@T
                       raise :class:`DeviceLostError` from tick T's decode
                       dispatch — the supervisor must hot-restart the
                       engine and replay every in-flight request
                       token-identically
    serve_hang@T[:SEC] sleep SEC (default 1.0) inside tick T — the tick
                       watchdog must fire and convert the stall into a
                       diagnosed restart

Lagged guard semantics under the decode ring (what an engine serves, at
depth 1; a scheduler built with ``async_depth=0`` reads each step in its own
tick and lags nothing): the injection still lands at
tick T's DISPATCH, but its observable consequence moves to the drain of
that step — up to the ring's depth in ticks later.  ``serve_nan``'s
non-finite flag is read at drain time (eviction one-or-more ticks late,
attribution unchanged); ``serve_raise`` surfaces when the dispatch
itself runs, and the supervisor drains the in-flight ring
(``flush_async``) before poison-bisecting so the probe sees a
state-consistent pool.  The isolation contract is identical either way:
exactly the faulted request fails, survivors stay bit-exact.

Fleet-side kinds (the ``step`` is the fleet router's monitor POLL index,
1-based — serving/router.py consults the injector once per health sweep):

    replica_down@P[:R] hard-kill replica R (default 0) at router poll P:
                       its in-flight requests fail with
                       ``ReplicaDownError`` and the router must fail them
                       over to a survivor with token-identical replay
    replica_hang@P[:SEC]
                       wedge replica 0's scheduler thread for SEC
                       (default 1.0) seconds at router poll P — no Python
                       progress, so only the heartbeat-staleness check
                       can see it; the router must mark the replica
                       unhealthy and hedge/fail over around it

Autoscaler-level kinds (P = autoscaler poll index, 1-based —
serving/autoscaler.py consults the injector once per control-loop poll):

    autoscale_hang@P[:SEC]
                       wedge the autoscaler's decision path for SEC
                       (default 1.0) seconds at its poll P — the world
                       keeps moving (flash crowd grows, replicas die)
                       while the controller sleeps; recovery contract is
                       that signals are re-read fresh AFTER the hang, so
                       a stale pre-hang view never drives a scale action

Disaggregation-level kinds (N = the disagg coordinator's KV TRANSFER
ordinal, 1-based — serving/disagg.py consults the injector once per
transfer attempt):

    kv_transfer_stall@N[:SEC]
                       sleep SEC (default 1.0) inside transfer N's
                       export on the source scheduler — the
                       coordinator's bounded deadline must trip and
                       degrade the request to the colocated path
    kv_transfer_corrupt@N
                       flip one byte of transfer N's first block payload
                       after its CRC-32 manifest is computed — the
                       importing scheduler must reject the block and the
                       request recomputes the suffix locally
    prefill_replica_down@N[:R]
                       hard-kill prefill replica R (default 0) as
                       transfer N begins, so the in-flight export dies —
                       the decode side must recompute locally and the
                       request never fails

Step-keyed faults (``nan_batch``/``kill_worker``/``stall_step``/
``sdc_flip``/``ckpt_corrupt``/the ``serve_*``, ``replica_*``, and
``kv_transfer_*``/``prefill_*`` families) are one-shot:
consumed when they fire, so a rollback replay of the same step index does
not re-trip them (the recovery itself must converge).

This module is import-light on purpose (stdlib only): the data pipeline and
serving stack consult it without pulling the JAX engine in.  The recovery
counters every subsystem bumps (``skipped_steps``, ``rollbacks``,
``ckpt_retries``, ``worker_respawns``, ``watchdog_fires``, ...) live in the
process-global telemetry registry (``telemetry/registry.py`` — also
stdlib-only); ``bump``/``counters``/``reset_counters`` here are the
stable API the fault layer and its tests were built on, now thin views of
that one ledger so the chaos tests and the telemetry snapshot read the
same numbers.
"""
from __future__ import annotations

import os
import re
import threading
from collections import Counter
from typing import Dict, List, Optional, Tuple

__all__ = [
    "ENV_VAR",
    "DeviceLostError",
    "FaultInjectionError",
    "FaultInjector",
    "get_injector",
    "install",
    "bump",
    "counters",
    "reset_counters",
]

ENV_VAR = "PDT_FAULT_SPEC"

_STEP_KINDS = (
    "nan_batch", "kill_worker", "stall_step", "kill_peer",
    "sdc_flip", "ckpt_corrupt",
    "serve_nan", "serve_raise", "serve_device_lost", "serve_hang",
    "replica_down", "replica_hang", "autoscale_hang",
    "kv_transfer_stall", "kv_transfer_corrupt", "prefill_replica_down",
)
_POINT_KINDS = {
    "ckpt_fail": "ckpt_save",
    "restore_fail": "ckpt_restore",
    "ckpt_async_fail": "ckpt_async_write",
}


class FaultInjectionError(OSError):
    """An injected I/O failure.

    Subclasses ``OSError`` so it lands in the default retry allowlist
    (``utils.retry.Retry``) exactly like the transient filesystem errors it
    stands in for.
    """


class DeviceLostError(FaultInjectionError):
    """Injected stand-in for losing the accelerator mid-dispatch.

    The serving supervisor classifies it (and real ``XlaRuntimeError``s)
    as non-attributable: no single request caused it, so the recovery is
    hot-restart + replay rather than poison-bisect.
    """


class FaultInjector:
    """Parsed fault spec, queryable by the instrumented call sites."""

    def __init__(self, spec: str = ""):
        self.spec = (spec or "").strip()
        # kind -> {step: arg}; one-shot entries popped when taken
        self._step_faults: Dict[str, Dict[int, float]] = {k: {} for k in _STEP_KINDS}
        # fail point -> [(first_attempt, n_failures)]
        self._fail_windows: Dict[str, List[Tuple[int, int]]] = {}
        self._attempts: Counter = Counter()
        # kind -> number of injected faults that actually FIRED (one-shot
        # takes and fail-point window hits); the soak oracle balances this
        # against pending() to prove no armed fault silently leaked
        self._fired: Counter = Counter()
        self._lock = threading.Lock()
        for raw in re.split(r"[;,]", self.spec):
            entry = raw.strip()
            if not entry:
                continue
            self._parse_entry(entry)

    def _parse_entry(self, entry: str) -> None:
        try:
            kind, rest = entry.split("@", 1)
            parts = rest.split(":", 1)
            step = int(parts[0])
            arg = parts[1] if len(parts) > 1 else None
        except ValueError:
            raise ValueError(
                f"bad {ENV_VAR} entry {entry!r}: want kind@step[:arg]"
            ) from None
        kind = kind.strip()
        if step < 0:
            raise ValueError(f"bad {ENV_VAR} entry {entry!r}: step must be >= 0")
        if kind in _POINT_KINDS:
            n = int(arg) if arg is not None else 1
            if n < 1:
                raise ValueError(
                    f"bad {ENV_VAR} entry {entry!r}: failure count must be >= 1"
                )
            self._fail_windows.setdefault(_POINT_KINDS[kind], []).append((step, n))
        elif kind in _STEP_KINDS:
            if kind in (
                "kill_worker", "serve_nan", "serve_raise", "sdc_flip",
                "replica_down", "prefill_replica_down",
            ):
                # arg = worker index / scheduler slot index / replica rank
                # / fleet replica index / prefill replica index (default 0)
                val = float(int(arg)) if arg is not None else 0.0
            elif kind == "kill_peer":
                # arg = target process index; -1 = whichever rank parses it
                val = float(int(arg)) if arg is not None else -1.0
            elif kind in ("stall_step", "serve_hang", "replica_hang",
                          "autoscale_hang", "kv_transfer_stall"):
                val = float(arg) if arg is not None else 1.0
            else:
                # nan_batch / serve_device_lost / ckpt_corrupt /
                # kv_transfer_corrupt take no arg
                if arg is not None:
                    raise ValueError(
                        f"bad {ENV_VAR} entry {entry!r}: {kind} takes no arg"
                    )
                val = 1.0
            if step in self._step_faults[kind]:
                raise ValueError(
                    f"bad {ENV_VAR} entry {entry!r}: duplicate {kind}@{step} "
                    f"(each kind@step pair may appear once per spec)"
                )
            self._step_faults[kind][step] = val
        else:
            raise ValueError(
                f"bad {ENV_VAR} entry {entry!r}: unknown kind {kind!r} "
                f"(want one of {sorted(_STEP_KINDS) + sorted(_POINT_KINDS)})"
            )

    @property
    def active(self) -> bool:
        return bool(self.spec)

    def take(self, kind: str, step: int) -> Optional[float]:
        """Consume the one-shot fault ``kind@step``; None when absent.

        Returns the entry's arg (worker index for ``kill_worker``, slot
        index for ``serve_nan``/``serve_raise``, stall seconds for
        ``stall_step``/``serve_hang``, 1.0 for the no-arg kinds).
        """
        with self._lock:
            val = self._step_faults[kind].pop(int(step), None)
            if val is not None:
                self._fired[kind] += 1
        if val is not None:
            bump(f"fault_fired_{kind}")
        return val

    def check_fail_point(self, point: str) -> None:
        """Raise :class:`FaultInjectionError` when this attempt ordinal of
        ``point`` (e.g. ``ckpt_save``) falls in an injected failure window."""
        with self._lock:
            ordinal = self._attempts[point]
            self._attempts[point] += 1
            windows = self._fail_windows.get(point, ())
        for first, n in windows:
            if first <= ordinal < first + n:
                with self._lock:
                    self._fired[point] += 1
                bump(f"injected_{point}_failures")
                raise FaultInjectionError(
                    f"injected {point} failure (attempt ordinal {ordinal}, "
                    f"window {first}+{n})"
                )

    def pending(self) -> Dict[str, List[int]]:
        """Armed faults that have NOT fired yet, ``kind -> sorted steps``.

        One-shot entries are listed by step index; fail-point windows by the
        attempt ordinals the process never reached.  A fault armed for a
        step/tick/attempt that never happens (engine drained or closed
        first) would otherwise vanish without a trace — the chaos soak
        oracle balances this against :meth:`fired` so every injected fault
        is accounted for as exactly one of fired-and-recovered or
        reported-unfired.
        """
        with self._lock:
            out: Dict[str, List[int]] = {
                kind: sorted(steps)
                for kind, steps in self._step_faults.items()
                if steps
            }
            for point, windows in self._fail_windows.items():
                seen = self._attempts[point]
                left = sorted(
                    o for first, n in windows
                    for o in range(first, first + n) if o >= seen
                )
                if left:
                    out[point] = left
        return out

    def fired(self) -> Dict[str, int]:
        """Counts of injected faults that actually fired, by kind/point."""
        with self._lock:
            return dict(self._fired)


# ---------------------------------------------------------------- process-global
_INJECTOR: Optional[FaultInjector] = None


def get_injector() -> FaultInjector:
    """The process injector; lazily parsed from ``PDT_FAULT_SPEC`` (inert
    when the variable is unset)."""
    global _INJECTOR
    if _INJECTOR is None:
        _INJECTOR = FaultInjector(os.environ.get(ENV_VAR, ""))
    return _INJECTOR


def install(spec: Optional[str]) -> FaultInjector:
    """Replace the process injector with one parsed from ``spec`` (the
    config-key path, and the test/bench hook).  ``install(None)`` resets to
    inert."""
    global _INJECTOR
    _INJECTOR = FaultInjector(spec or "")
    return _INJECTOR


def bump(name: str, n: int = 1) -> None:
    """Increment a process-global recovery counter (thread-safe)."""
    from ..telemetry.registry import get_registry

    get_registry().counter(name).inc(n)


def counters() -> Dict[str, int]:
    """Snapshot of all process counters (the shared telemetry ledger)."""
    from ..telemetry.registry import get_registry

    return {k: v for k, v in get_registry().counters().items() if v}


def reset_counters() -> None:
    from ..telemetry.registry import reset_registry

    reset_registry()


def poison_batches(host_iter, injector: FaultInjector, start_iter: int = 0,
                   logger=None):
    """Wrap a training batch iterator, applying ``nan_batch`` faults.

    Yields batches unchanged except at injected step indices, where the
    (float) image/token-input half is replaced with NaNs — the on-device
    anomaly guard must then skip the step.  Counting starts at
    ``start_iter`` and stays aligned with the step index because the
    training stream is strictly ordered (``device_prefetch`` preserves
    order; a rebuilt stream passes its new start iter).
    """
    import numpy as np

    step = start_iter
    for img, label in host_iter:
        if injector.take("nan_batch", step) is not None:
            img = np.asarray(img)
            if np.issubdtype(img.dtype, np.floating):
                img = np.full(img.shape, np.nan, dtype=img.dtype)
                bump("injected_nan_batches")
                if logger is not None:
                    logger.warning("fault injection: NaN batch at step %d", step)
            elif logger is not None:
                logger.warning(
                    "fault injection: nan_batch@%d skipped — batch dtype %s "
                    "cannot carry NaN (float pipelines only)", step, img.dtype
                )
        step += 1
        yield img, label
