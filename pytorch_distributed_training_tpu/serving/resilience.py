"""Serving-side fault tolerance: classify, isolate, restart — never hang.

The training fault layer (PRs 3-5) follows one house style: deterministic
injection (engine/fault.py) → guard → bounded recovery → counters → chaos
bench.  This module is the serving half.  Before PR 9 a tick exception
failed EVERY in-flight request (`ContinuousScheduler._fail_inflight`);
now the supervisor sits between the tick and that scorched-earth
fallback and walks a recovery ladder:

1. **Attributable errors → poison-bisect.**  A Python exception raised
   from the decode dispatch while requests are active is re-driven
   against halves of the active set (``_decode_probe`` re-runs the exact
   dispatch — the pool scatter is idempotent for identical inputs, and
   per-row per-token-index ``fold_in`` sampling keys make the probe
   bit-reproducible).  The culprit is evicted with a diagnosed
   :class:`PoisonedRequestError`; its KV blocks free; every other slot
   resumes untouched.  ~log2(slots) probes, plus one reproduce and one
   confirm.  A NaN-emitting request never even raises: the decode
   programs return per-row ``isfinite`` flags (serving/decode.py) and
   the scheduler evicts on the flag — the serving mirror of the training
   anomaly guard.
2. **Non-attributable errors → hot-restart with replay.**  Device loss
   (:class:`..engine.fault.DeviceLostError`, real ``XlaRuntimeError``),
   a hung tick (:class:`HungTickError` from the tick watchdog), a
   non-reproducible probe, or a LOST POOL escalate to
   ``_rebuild_and_requeue``.  The decode programs consume the pool they
   are given (serving/decode.py), so a call that raises after its
   dispatch can leave the scheduler's pool deleted; injected faults raise
   before the dispatch and leave it whole.  No probe can run on a deleted
   pool (each would raise, and the bisect would name whoever was left),
   so ``_pool_lost()`` is asked before the bisect and again before its
   verdict.  The compiled prefill/decode programs and the paged pool are
   rebuilt and every in-flight request is re-admitted; the scheduler
   re-prefills ``prompt + tokens_generated_so_far`` and re-feeds the generated
   tokens through the SAME decode program that produced them, so the
   continuation is token-identical (the replay parity oracle pins it
   bitwise, greedy and sampled).
3. **Bounded budget.**  Restarts draw from ``max_restarts``; exhaustion
   fails the remaining futures with :class:`EngineRestartError` chaining
   the final cause — bounded recovery, exactly like the training-side
   rollback/retry budgets.

The supervisor holds POLICY and BUDGET only; all slot/pool mutation
stays on the scheduler thread (``handle_tick_failure`` runs inside
``tick``'s except clause), so the pool keeps its no-locks contract.
Only the counters read cross-thread (health endpoints) sit under the
supervisor's lock.
"""
from __future__ import annotations

import logging
from typing import Optional

import threading

from ..engine import fault
from ..telemetry.spans import span

__all__ = [
    "EngineRestartError",
    "HungTickError",
    "PoisonedRequestError",
    "ServingSupervisor",
]


class PoisonedRequestError(RuntimeError):
    """One request poisoned the decode step; only ITS future gets this.

    Raised with a diagnosis (slot, tick, trigger) and chained to the
    underlying cause when there was a Python exception (``__cause__`` is
    None for the isfinite output-guard path — NaNs never raise).
    """


class HungTickError(RuntimeError):
    """The tick watchdog flagged a scheduler iteration as hung.

    Converted into a diagnosed hot-restart by the supervisor: a wedged
    decode dispatch cannot be attributed to one request, and the
    compiled programs' state is suspect.
    """


class EngineRestartError(RuntimeError):
    """The restart budget is exhausted; remaining futures fail with this,
    ``__cause__`` chaining the error that burned the last restart."""


def _is_device_loss(exc: BaseException) -> bool:
    """Device-level failure: the error names the runtime, not a request."""
    if isinstance(exc, (fault.DeviceLostError, HungTickError)):
        return True
    name = type(exc).__name__
    module = type(exc).__module__ or ""
    return "XlaRuntimeError" in name or module.startswith("jaxlib")


class ServingSupervisor:
    """Recovery policy + restart budget for one :class:`ContinuousScheduler`.

    ``handle_tick_failure`` MUST be called on the scheduler thread (it
    drives slot eviction and pool rebuild); ``restarts()`` / ``exhausted()``
    are safe from any thread and feed the health snapshot.
    """

    def __init__(
        self,
        scheduler,
        *,
        max_restarts: int = 2,
        poison_bisect: bool = True,
        logger: Optional[logging.Logger] = None,
    ):
        if max_restarts < 0:
            raise ValueError(f"max_restarts must be >= 0, got {max_restarts}")
        self._sched = scheduler
        self.max_restarts = int(max_restarts)
        self.poison_bisect = bool(poison_bisect)
        self._logger = logger or logging.getLogger(__name__)
        self._lock = threading.Lock()
        self._restarts = 0  # guarded by: self._lock
        self._exhausted = False  # guarded by: self._lock

    def restarts(self) -> int:
        with self._lock:
            return self._restarts

    def exhausted(self) -> bool:
        with self._lock:
            return self._exhausted

    # ------------------------------------------------------------------ #

    def handle_tick_failure(self, exc: BaseException) -> bool:
        """Recover from a failed tick; returns True (work happened).

        Ladder: device-class errors restart; decode-phase errors bisect
        down to one request and evict it; anything unattributable (prefill
        phase, non-reproducible, bisect disabled with several suspects)
        escalates to restart.  Restart past the budget fails the world
        with the chained cause.
        """
        sched = self._sched
        # the scheduler flushed its async dispatch ring before handing
        # us the failure (scheduler.tick), so probe/replay state below
        # is sync-equivalent: host-known streams match the device, and
        # dispatch counters are rolled back to gen_idx.
        if (
            not _is_device_loss(exc) and sched._tick_phase == "decode"
            and not sched._pool_lost()
        ):
            # span = the serve-side MTTR anchor (telemetry/slo.py): recovery
            # start → first post-recovery decode tick
            with span("poison_bisect", step=sched._tick_no,
                      cause=type(exc).__name__):
                isolated = self._isolate(exc)
            if isolated:
                return True
            self._logger.warning(
                "decode failure not attributable to one request "
                "(%s: %s) — escalating to hot-restart",
                type(exc).__name__, exc,
            )
        return self._restart(exc)

    # ------------------------------------------------------------------ #

    def _probe_raises(self, reqs) -> bool:
        self._sched._bump("poison_probes")
        try:
            self._sched._decode_probe(reqs)
        except Exception:
            return True
        return False

    def _isolate(self, exc: BaseException) -> bool:
        """Bisect the active set down to the request that reproduces
        ``exc``'s dispatch failure and evict it; False = cannot attribute."""
        sched = self._sched
        active = [r for r in sched._slots if r is not None]
        if not active:
            return False
        if len(active) == 1:
            # nothing to bisect: the only active request owns the failure
            sched._evict_poisoned(active[0], cause=exc, trigger="decode raise")
            return True
        if not self.poison_bisect:
            return False
        if not self._probe_raises(active):
            return False  # not reproducible — transient, restart instead
        cands = active
        while len(cands) > 1:
            half = cands[: len(cands) // 2]
            cands = half if self._probe_raises(half) else cands[len(cands) // 2 :]
        if not self._probe_raises(cands):
            return False  # the fault needed company — not one request's
        if sched._pool_lost():
            return False  # a probe lost the pool: every later one raised
        sched._evict_poisoned(cands[0], cause=exc, trigger="decode raise")
        return True

    def _restart(self, cause: BaseException) -> bool:
        sched = self._sched
        with self._lock:
            if self._restarts >= self.max_restarts:
                self._exhausted = True
                n = self._restarts
            else:
                self._restarts += 1
                n = -1
        if n >= 0:
            sched._bump("restart_budget_exhausted")
            err = EngineRestartError(
                f"serving engine restart budget exhausted ({n}/"
                f"{self.max_restarts} restarts used); failing in-flight "
                "requests"
            )
            err.__cause__ = cause
            self._logger.error("%s", err)
            sched._fail_inflight(err)
            return True
        sched._bump("engine_restarts")
        self._logger.error(
            "hot-restarting serving engine after %s: %s (restart %d/%d)",
            type(cause).__name__, cause, self.restarts(), self.max_restarts,
        )
        with span("serving_restart", step=sched._tick_no,
                  cause=type(cause).__name__):
            sched._rebuild_and_requeue()
        return True
