"""Chaos soak engine: seeded multi-fault scenarios + recovery-SLO oracles.

Every recovery ladder in the repo — anomaly guard, hung-step watchdog,
retrying/async checkpoints, elastic peer loss, integrity sentinel, serving
poison-bisect/hot-restart, fleet failover — is proved one fault at a time
by its own tests.  At pod scale failures *overlap*: a rank dies
while an async write is in flight, an SDC flip lands during post-rollback
replay, a request poisons the engine mid-drain.  This module provokes the
compound cases deterministically and holds each scenario to shared
invariant oracles plus measured recovery SLOs.

Three layers:

**Fault menu + coverage matrix.**  :data:`FAULT_MENU` declares every
registered fault kind (pinned against ``fault._STEP_KINDS`` /
``fault._POINT_KINDS`` by a tier-1 test) with its family, the recovery
path that must consume it, the counters that attribute a fired instance,
and whether the ladder guarantees final-state bit parity against an
uninjected twin.  Adding a fault kind to ``engine/fault.py`` without soak
coverage fails the matrix test.

**Seeded scenario generator.**  :class:`ScenarioGenerator` composes 2-4
faults per scenario from family-specific TEMPLATES (compatibility-checked
atom groups — e.g. ``restore_fail`` only rides with a rollback burst that
actually restores; ``ckpt_corrupt`` is anchored to a save step that a
later burst's restore will hit) with controlled temporal overlap
(``sequential`` / ``adjacent`` / ``concurrent``).  All randomness flows
from one explicit ``random.Random(seed)`` — no wall clock, no module
state — so the same seed yields a byte-identical scenario schedule
(:meth:`ScenarioGenerator.schedule_json`).

**Soak runner + oracles.**  :class:`ChaosSoakEngine` runs each scenario
through the REAL Runner (train), the real continuous scheduler driven
through its ``drain(deadline_ms)`` window (serve), a 2-process
``multihost_worker`` pair (elastic), or a :class:`ServingFleet` (fleet),
then checks:

- *fault accounting*: every injected fault fired exactly once and its
  recovery counters moved (``FaultInjector.fired``/``pending`` balance —
  an armed fault the engine never reached is a scenario failure, not a
  silent no-op);
- *bit parity* vs a cached uninjected twin where every fault in the
  scenario guarantees it (train params digest; per-request token streams
  for serve);
- *lifecycle audit*: no leaked threads after teardown,
  ``kv_pool.check_invariants()`` green through and after the drain;
- *goodput floor* from the PR 6 telemetry and per-scenario **MTTR** from
  trace spans (telemetry/slo.py): recovery-span start to the end of the
  first productive step/tick after it.

``tests/test_chaos_soak.py`` drives ``ChaosSoakEngine.run()``; its summary
carries per-scenario MTTR, goodput ratio, recovery counters and the
coverage matrix.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import subprocess
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from random import Random
from typing import Dict, List, Optional, Sequence, Tuple

from . import fault
from .fault import _POINT_KINDS, _STEP_KINDS

__all__ = [
    "FAULT_MENU",
    "FaultEntry",
    "FaultKind",
    "ChaosSoakEngine",
    "Scenario",
    "ScenarioGenerator",
    "coverage_matrix",
    "disagg_cells",
    "scaling_cells",
    "uncovered_kinds",
]

FAMILIES = ("train", "serve", "elastic", "fleet", "scaling", "disagg")

OVERLAP_MODES = ("sequential", "adjacent", "concurrent")


@dataclass(frozen=True)
class FaultKind:
    """One registered fault kind's place in the soak coverage matrix."""

    name: str
    family: str       # which scenario family exercises it
    recovery: str     # the ladder that must consume a fired instance
    counters: Tuple[str, ...]  # registry counters attributing the recovery
    parity: bool      # final-state bit parity vs uninjected twin guaranteed


# The single source of truth tying every fault kind to its consuming
# ladder.  test_chaos_soak.py pins this against fault.py's kind registry:
# a kind added there without a row here (or a row without template
# coverage) fails tier-1.
FAULT_MENU: Dict[str, FaultKind] = {
    k.name: k
    for k in (
        FaultKind("nan_batch", "train", "anomaly_skip_or_rollback",
                  ("skipped_steps",), parity=False),
        FaultKind("kill_worker", "train", "worker_respawn",
                  ("worker_respawns",), parity=True),
        FaultKind("stall_step", "train", "hang_watchdog",
                  ("watchdog_fires",), parity=True),
        FaultKind("sdc_flip", "train", "integrity_restore",
                  ("integrity_transient_flips",), parity=True),
        FaultKind("ckpt_corrupt", "train", "manifest_reject_fallback",
                  ("integrity_manifest_rejects", "ckpt_fallbacks"),
                  parity=False),
        FaultKind("ckpt_fail", "train", "ckpt_retry",
                  ("ckpt_retries",), parity=True),
        FaultKind("ckpt_async_fail", "train", "ckpt_retry",
                  ("ckpt_retries",), parity=True),
        FaultKind("restore_fail", "train", "ckpt_retry",
                  ("ckpt_retries",), parity=True),
        FaultKind("kill_peer", "elastic", "elastic_heartbeat_emergency_save",
                  ("peer_lost", "elastic_saves"), parity=False),
        FaultKind("serve_nan", "serve", "output_guard_evict",
                  ("requests_poisoned",), parity=True),
        FaultKind("serve_raise", "serve", "poison_bisect",
                  ("requests_poisoned",), parity=True),
        FaultKind("serve_device_lost", "serve", "hot_restart_replay",
                  ("engine_restarts",), parity=True),
        FaultKind("serve_hang", "serve", "tick_watchdog_restart",
                  ("serve_watchdog_fires", "engine_restarts"), parity=True),
        FaultKind("replica_down", "fleet", "fleet_failover_replay",
                  ("serving_fleet_replicas_down",), parity=True),
        FaultKind("replica_hang", "fleet", "heartbeat_staleness_failover",
                  ("injected_replica_hangs",), parity=True),
        FaultKind("autoscale_hang", "scaling", "decision_reread_after_hang",
                  ("injected_autoscale_hangs",), parity=True),
        FaultKind("kv_transfer_stall", "disagg", "transfer_deadline_degrade",
                  ("serving_disagg_deadline_degrades",), parity=True),
        FaultKind("kv_transfer_corrupt", "disagg", "checksum_reject_recompute",
                  ("serving_disagg_rejects",), parity=True),
        FaultKind("prefill_replica_down", "disagg",
                  "prefill_death_local_recompute",
                  ("serving_disagg_transfer_recomputes",), parity=True),
    )
}


def coverage_matrix() -> Dict[str, Dict[str, str]]:
    """``kind -> {family, recovery}`` — the kind × recovery-path matrix."""
    return {
        name: {"family": k.family, "recovery": k.recovery}
        for name, k in sorted(FAULT_MENU.items())
    }


def registered_fault_kinds() -> Tuple[str, ...]:
    """All kinds fault.py can inject (step kinds + fail-point kinds)."""
    return tuple(sorted(set(_STEP_KINDS) | set(_POINT_KINDS)))


def uncovered_kinds() -> List[str]:
    """Registered fault kinds absent from the soak scenario space.

    Non-empty means a fault kind exists that no generator template can
    produce — the tier-1 matrix test fails on it.
    """
    covered = set()
    for fam in FAMILIES:
        for template in _TEMPLATES[fam]:
            # scaling atoms carry a phase prefix ("up:replica_down");
            # coverage is about the KIND, whatever window it lands in
            covered.update(a.split(":")[-1] for a in template)
    return sorted((set(registered_fault_kinds()) | set(FAULT_MENU))
                  - covered)


def scaling_cells() -> Dict[str, List[str]]:
    """``scaling-event phase -> fault kinds`` the scenario space can land
    in that window.  The three phases are the scaling-event cells of the
    coverage matrix: ``scale_up`` (fault mid-flash-crowd while capacity
    is being added), ``drain`` (fault inside a scale-down drain), and
    ``decision`` (the autoscaler's own control loop wedged).  Pinned
    non-empty by tier-1 so scaling coverage cannot silently regress."""
    cells: Dict[str, set] = {"scale_up": set(), "drain": set(),
                             "decision": set()}
    phase_of = {"up": "scale_up", "drain": "drain", "decision": "decision"}
    for template in _TEMPLATES["scaling"]:
        for atom in template:
            phase, _, kind = atom.partition(":")
            cells[phase_of[phase]].add(kind)
    return {k: sorted(v) for k, v in cells.items()}


def disagg_cells() -> Dict[str, List[str]]:
    """``disaggregation phase -> fault kinds`` the scenario space can
    land in that window.  ``transfer`` covers the KV-transfer edge
    (stall past deadline, corrupt payload, prefill death mid-export);
    ``handoff`` covers decode death while a just-staged request is being
    handed to its replica.  Pinned non-empty by tier-1 so disagg
    coverage cannot silently regress."""
    cells: Dict[str, set] = {"transfer": set(), "handoff": set()}
    for template in _TEMPLATES["disagg"]:
        for atom in template:
            phase, _, kind = atom.partition(":")
            cells[phase].add(kind)
    return {k: sorted(v) for k, v in cells.items()}


# ------------------------------------------------------------------ scenarios
@dataclass(frozen=True)
class FaultEntry:
    kind: str
    step: int
    arg: Optional[str] = None

    def render(self) -> str:
        base = f"{self.kind}@{self.step}"
        return base if self.arg is None else f"{base}:{self.arg}"


@dataclass(frozen=True)
class Scenario:
    index: int
    family: str
    template: Tuple[str, ...]
    overlap: str
    entries: Tuple[FaultEntry, ...]

    def spec(self) -> str:
        return ";".join(e.render() for e in self.entries)

    def kinds(self) -> Tuple[str, ...]:
        return tuple(sorted({e.kind for e in self.entries}))

    @property
    def parity_expected(self) -> bool:
        return all(FAULT_MENU[k].parity for k in self.kinds())

    def to_dict(self) -> Dict:
        return {
            "index": self.index,
            "family": self.family,
            "template": list(self.template),
            "overlap": self.overlap,
            "spec": self.spec(),
            "parity_expected": self.parity_expected,
        }


# Family templates: compatible atom groups, each yielding 2-4 fault
# entries.  Atoms with placement constraints (restore_fail needs the
# burst's restore; ckpt_corrupt must poison the exact save the burst
# rolls back to) are anchored inside _place_train rather than free.
_TEMPLATES: Dict[str, Tuple[Tuple[str, ...], ...]] = {
    "train": (
        ("nan_batch", "stall_step"),
        ("nan_batch", "kill_worker", "sdc_flip"),
        ("kill_worker", "stall_step", "ckpt_async_fail"),
        ("sdc_flip", "ckpt_async_fail"),
        ("sdc_flip", "stall_step", "ckpt_fail"),
        ("nan_burst", "ckpt_async_fail"),
        ("nan_burst", "restore_fail"),
        ("nan_burst", "ckpt_corrupt"),
        ("sdc_flip", "nan_burst"),
    ),
    "serve": (
        ("serve_raise", "serve_nan"),
        ("serve_device_lost", "serve_raise"),
        ("serve_hang", "serve_nan"),
        ("serve_device_lost", "serve_nan", "serve_raise"),
        ("serve_hang", "serve_raise"),
    ),
    "elastic": (
        ("ckpt_fail", "kill_peer"),
        ("stall_step", "ckpt_fail", "kill_peer"),
    ),
    "fleet": (
        ("replica_down", "serve_device_lost"),
        ("replica_hang", "serve_device_lost"),
    ),
    # scaling atoms are "<phase>:<kind>": the phase names the scaling-
    # event window the fault must land in (scale-up mid-flash-crowd,
    # scale-down drain, autoscaler decision poll) — _run_scaling installs
    # each phase's entries only once its window opens
    "scaling": (
        ("up:replica_down", "decision:autoscale_hang"),
        ("drain:serve_nan", "decision:autoscale_hang"),
        ("up:replica_down", "drain:serve_raise"),
        ("drain:serve_raise", "decision:autoscale_hang"),
    ),
    # disagg atoms are "<phase>:<kind>": transfer-phase kinds key on the
    # coordinator's 1-based KV-transfer ordinal; the handoff-phase
    # replica_down keys on the router poll clock exactly as in the fleet
    # family (decode death while staged requests are in flight)
    "disagg": (
        ("transfer:kv_transfer_corrupt", "transfer:kv_transfer_stall"),
        ("transfer:prefill_replica_down", "transfer:kv_transfer_corrupt"),
        ("transfer:kv_transfer_stall", "handoff:replica_down"),
        ("transfer:prefill_replica_down", "transfer:kv_transfer_stall",
         "transfer:kv_transfer_corrupt"),
    ),
}

# train scenario geometry (must match ChaosSoakEngine._train_cfg)
_TRAIN_ITERS = 12
_TRAIN_CKPT_INTERVAL = 3          # saves at steps 2, 5, 8, 11
_ANOMALY_MAX_CONSEC = 3
# serve fault ticks must land while the 4 submitted requests are still
# decoding (max_new_tokens=6 -> the run retires around tick 7-8); hang
# ticks additionally sit past the tick watchdog's warmup=3
_SERVE_TICK_LO, _SERVE_TICK_HI = 2, 5
_SERVE_HANG_LO, _SERVE_HANG_HI = 4, 6


class ScenarioGenerator:
    """Deterministic scenario schedules from one explicit seed."""

    def __init__(self, seed: int, families: Sequence[str] = ("train", "serve")):
        bad = sorted(set(families) - set(FAMILIES))
        if bad:
            raise ValueError(
                f"unknown chaos families {bad} (want subset of {FAMILIES})"
            )
        if not families:
            raise ValueError("chaos generator needs at least one family")
        self.seed = int(seed)
        self.families = tuple(families)

    # ------------------------------------------------------------- placement
    def _positions(self, rng: Random, n: int, overlap: str,
                   lo: int, hi: int) -> List[int]:
        """``n`` DISTINCT step indices in ``[lo, hi]`` per overlap mode.

        ``concurrent`` packs them into a 2-wide window (distinct steps —
        ``kind@step`` pairs must stay unique per spec — but temporally
        overlapping recoveries); ``adjacent`` makes them consecutive;
        ``sequential`` spreads them ≥ 2 apart where room allows.
        """
        span = hi - lo
        if overlap == "concurrent":
            base = rng.randint(lo, max(lo, hi - max(n - 1, 1)))
            return [min(base + i, hi) for i in range(n)]
        if overlap == "adjacent":
            base = rng.randint(lo, max(lo, hi - (n - 1)))
            return [min(base + i, hi) for i in range(n)]
        stride = max(2, span // max(n, 1))
        start = rng.randint(lo, max(lo, hi - stride * (n - 1)))
        return [min(start + i * stride, hi) for i in range(n)]

    def _place_train(self, rng: Random, template: Tuple[str, ...],
                     overlap: str) -> List[FaultEntry]:
        entries: List[FaultEntry] = []
        free: List[str] = []
        burst_at: Optional[int] = None
        for atom in template:
            if atom == "nan_burst":
                # 3 consecutive nan batches trip max_consecutive=3 ->
                # rollback.  Anchored after the step-5 save and ending
                # before the last iters so replay has productive steps
                # (the MTTR endpoint) left to measure.
                burst_at = 6
                entries.extend(
                    FaultEntry("nan_batch", burst_at + i) for i in range(3)
                )
            elif atom == "ckpt_corrupt":
                # poison the save the burst's restore will hit (step 5 —
                # the newest save before the burst), forcing the manifest
                # reject -> fallback-to-step-2 ladder
                entries.append(FaultEntry("ckpt_corrupt", 5))
            elif atom == "restore_fail":
                # the burst's rollback performs restore attempt 0
                entries.append(FaultEntry("restore_fail", 0, "1"))
            elif atom in ("ckpt_fail", "ckpt_async_fail"):
                entries.append(FaultEntry(atom, rng.randint(0, 1), "1"))
            else:
                free.append(atom)
        if free:
            # free atoms sit past the watchdog warmup (3 recorded steps)
            # and, when a burst is present, BEFORE it — an sdc flip must be
            # caught at the step-3 integrity check, not mid-burst where the
            # restore would reset the anomaly streak and defuse the
            # rollback the scenario is predicated on
            lo, hi = (2, 3) if burst_at is not None else (4, _TRAIN_ITERS - 4)
            for atom, step in zip(
                free, self._positions(rng, len(free), overlap, lo, hi)
            ):
                if atom == "nan_batch":
                    entries.append(FaultEntry("nan_batch", step))
                elif atom == "kill_worker":
                    entries.append(FaultEntry("kill_worker", step, "0"))
                elif atom == "stall_step":
                    # the watchdog only sees stall + step compute (the
                    # checkpoint write lands outside the started/finished
                    # window), and its limit = 4 x trailing-median ranges
                    # ~0.6-1.9s for this workload — the stall must clear
                    # the top of that band decisively or the fire becomes
                    # a coin flip on machine load
                    entries.append(FaultEntry(
                        "stall_step", step, f"{rng.uniform(2.8, 3.2):.2f}"
                    ))
                elif atom == "sdc_flip":
                    entries.append(FaultEntry("sdc_flip", step, "0"))
        return entries

    def _place_serve(self, rng: Random, template: Tuple[str, ...],
                     overlap: str) -> List[FaultEntry]:
        entries: List[FaultEntry] = []
        free = [a for a in template if a != "serve_hang"]
        if "serve_hang" in template:
            entries.append(FaultEntry(
                "serve_hang", rng.randint(_SERVE_HANG_LO, _SERVE_HANG_HI),
                f"{rng.uniform(0.5, 0.8):.2f}",
            ))
        ticks = self._positions(
            rng, len(free), overlap, _SERVE_TICK_LO, _SERVE_TICK_HI
        )
        # each poison fault gets its OWN slot: after a bisect/guard
        # eviction the culprit's slot stays empty for the rest of the run,
        # and a later fault aimed at an empty slot is dropped unfired
        slot = 0
        for atom, tick in zip(free, ticks):
            if atom in ("serve_raise", "serve_nan"):
                entries.append(FaultEntry(atom, tick, str(slot)))
                slot += 1
            else:  # serve_device_lost
                entries.append(FaultEntry(atom, tick))
        return entries

    def _place_elastic(self, rng: Random, template: Tuple[str, ...],
                       overlap: str) -> List[FaultEntry]:
        del overlap  # the peer kill dominates; windows are anchored
        entries = []
        for atom in template:
            if atom == "kill_peer":
                entries.append(FaultEntry("kill_peer", rng.randint(4, 6), "0"))
            elif atom == "ckpt_fail":
                entries.append(FaultEntry("ckpt_fail", 0, "1"))
            elif atom == "stall_step":
                entries.append(FaultEntry(
                    "stall_step", 2, f"{rng.uniform(0.2, 0.4):.2f}"
                ))
        return entries

    def _place_fleet(self, rng: Random, template: Tuple[str, ...],
                     overlap: str) -> List[FaultEntry]:
        del overlap
        entries = []
        for atom in template:
            if atom == "replica_down":
                entries.append(FaultEntry(
                    "replica_down", rng.randint(2, 4), "0"
                ))
            elif atom == "replica_hang":
                # long enough that the router's heartbeat-staleness check
                # (timeout 5.0s in _run_fleet's config) sees the wedge and
                # hedges around it; the wedge must outlast that clock plus
                # slack, hence 6.5-8s — sub-threshold stalls are the serve
                # family's serve_hang territory, not this fault's
                entries.append(FaultEntry(
                    "replica_hang", rng.randint(2, 4),
                    f"{rng.uniform(6.5, 8.0):.2f}",
                ))
            else:  # serve_device_lost rides on whichever replica ticks first
                entries.append(FaultEntry(
                    "serve_device_lost", rng.randint(2, 4)
                ))
        return entries

    def _place_scaling(self, rng: Random, template: Tuple[str, ...],
                       overlap: str) -> List[FaultEntry]:
        """One entry per phase-prefixed atom, IN TEMPLATE ORDER (the
        runner recovers each entry's phase by zipping the template with
        the entries).  Steps are window-relative: _run_scaling shifts
        them past whatever the warmup consumed when the window opens."""
        del overlap  # phases impose the temporal structure here
        entries = []
        for atom in template:
            phase, _, kind = atom.partition(":")
            if phase == "decision":
                # the autoscaler's FIRST poll is the scale-up decision
                # mid-flash-crowd — the one worth wedging.  The hang must
                # be long enough that the world visibly moved under the
                # sleeping controller, short enough to keep the soak fast.
                entries.append(FaultEntry(
                    kind, 1, f"{rng.uniform(0.3, 0.6):.2f}"
                ))
            elif phase == "up":
                # kill the replica the scale-up just added (index 1 — the
                # first replica ever added to a 1-replica fleet) while
                # flash-crowd requests are in flight on it
                entries.append(FaultEntry(kind, rng.randint(1, 3), "1"))
            else:  # drain: poison a decoding slot mid-scale-down-drain
                entries.append(FaultEntry(kind, rng.randint(1, 2), "0"))
        return entries

    def _place_disagg(self, rng: Random, template: Tuple[str, ...],
                      overlap: str) -> List[FaultEntry]:
        """Transfer-phase entries key on the coordinator's 1-based
        transfer ordinal; the handoff replica_down keys on router polls.

        Ordinals are assigned deterministically: _run_disagg serializes
        transfers (one worker, single-flight, distinct prefix groups) so
        ordinal K is exactly the Kth staged request.  prefill_replica_
        down is pinned to ordinal 1 — the directory starts empty, so the
        first transfer is always prefill-sourced (later ordinals may be
        replica-to-replica, where no prefill is in the path and the
        fault would go unfired)."""
        del overlap  # the ordinal clock imposes the temporal structure
        entries = []
        next_ord = 2  # ordinal 1 is reserved for prefill_replica_down
        for atom in template:
            _, _, kind = atom.partition(":")
            if kind == "prefill_replica_down":
                entries.append(FaultEntry(kind, 1, "0"))
            elif kind == "kv_transfer_stall":
                # decisively past the 800 ms transfer deadline the
                # runner configures, far below any request deadline
                entries.append(FaultEntry(
                    kind, next_ord, f"{rng.uniform(1.5, 2.0):.2f}"
                ))
                next_ord += 1
            elif kind == "kv_transfer_corrupt":
                entries.append(FaultEntry(kind, next_ord))
                next_ord += 1
            else:  # handoff:replica_down — decode death, poll-keyed
                entries.append(FaultEntry(kind, rng.randint(2, 4), "0"))
        return entries

    # ------------------------------------------------------------ generation
    def generate(self, n: int) -> List[Scenario]:
        """``n`` scenarios, round-robin over the configured families.

        A fresh ``Random(seed)`` per call: ``generate(n)`` is a pure
        function of ``(seed, families, n)``.
        """
        if n < 1:
            raise ValueError(f"need n >= 1 scenarios, got {n}")
        rng = Random(self.seed)
        place = {
            "train": self._place_train,
            "serve": self._place_serve,
            "elastic": self._place_elastic,
            "fleet": self._place_fleet,
            "scaling": self._place_scaling,
            "disagg": self._place_disagg,
        }
        out: List[Scenario] = []
        for i in range(n):
            family = self.families[i % len(self.families)]
            template = rng.choice(_TEMPLATES[family])
            overlap = rng.choice(OVERLAP_MODES)
            entries = place[family](rng, template, overlap)
            if not 2 <= len(entries) <= 4:
                raise AssertionError(
                    f"template {template} produced {len(entries)} faults "
                    "(scenario contract is 2-4)"
                )
            # the spec must parse as a whole (duplicate/arity validation)
            scn = Scenario(i, family, tuple(template), overlap,
                           tuple(entries))
            fault.FaultInjector(scn.spec())
            out.append(scn)
        return out

    def schedule_json(self, n: int) -> str:
        """Byte-stable schedule: same seed ⇒ identical string."""
        return json.dumps(
            [s.to_dict() for s in self.generate(n)],
            sort_keys=True, separators=(",", ":"),
        )


# ------------------------------------------------------------------ soak run
class ChaosSoakEngine:
    """Run seeded scenarios through the real engines and check oracles."""

    def __init__(
        self,
        seed: int = 0,
        families: Sequence[str] = ("train", "serve"),
        goodput_floor: float = 0.05,
        logger: Optional[logging.Logger] = None,
    ):
        self.generator = ScenarioGenerator(seed, families)
        self.goodput_floor = float(goodput_floor)
        self.logger = logger or logging.getLogger(__name__)
        # one uninjected twin per distinct run configuration, shared by
        # every scenario needing that baseline — what makes a 20-scenario
        # soak affordable
        self._twins: Dict[Tuple, Dict] = {}

    # --------------------------------------------------------------- helpers
    @staticmethod
    def _params_digest(params) -> str:
        import jax
        import numpy as np

        h = hashlib.sha256()
        for leaf in jax.tree.leaves(jax.tree.map(np.asarray, params)):
            h.update(np.ascontiguousarray(leaf).tobytes())
        return h.hexdigest()

    @staticmethod
    def _read_jsonl(path: str) -> List[Dict]:
        try:
            with open(path) as fp:
                return [json.loads(ln) for ln in fp if ln.strip()]
        except OSError:
            return []

    # threads this codebase starts and is responsible for joining; library
    # pools (orbax asyncio executors, grpc, tqdm monitors) reuse anonymous
    # workers across runs and are not a lifecycle leak
    _OWNED_THREAD_PREFIXES = (
        "serving-", "ckpt-async-writer", "step-watchdog", "fleet-",
        "elastic-", "router-", "heartbeat", "disagg-",
    )

    @staticmethod
    def _thread_baseline() -> set:
        return {t.ident for t in threading.enumerate()}

    @classmethod
    def _leaked_threads(cls, baseline: set, settle_s: float = 5.0) -> List[str]:
        """OWNED threads alive past teardown that were not there before."""
        deadline = time.monotonic() + settle_s
        while True:
            extra = [
                t for t in threading.enumerate()
                if t.ident not in baseline and t.is_alive()
                and t.name.startswith(cls._OWNED_THREAD_PREFIXES)
            ]
            if not extra or time.monotonic() >= deadline:
                break
            time.sleep(0.05)
        return sorted(t.name for t in extra)

    def _check_accounting(self, scn: Scenario, injector,
                          counters: Dict[str, int],
                          failures: List[str]) -> None:
        """Fired/pending balance + per-kind recovery-counter attribution."""
        pending = injector.pending()
        if pending:
            failures.append(f"faults never fired: {pending}")
        fired = injector.fired()
        want = {}
        for e in scn.entries:
            key = _POINT_KINDS.get(e.kind, e.kind)
            want[key] = want.get(key, 0) + 1
        for key, n in want.items():
            if fired.get(key, 0) < n:
                failures.append(
                    f"{key}: fired {fired.get(key, 0)} of {n} injected"
                )
        for kind in scn.kinds():
            menu = FAULT_MENU[kind]
            if not any(counters.get(c, 0) > 0 for c in menu.counters):
                failures.append(
                    f"{kind}: no recovery attribution (none of "
                    f"{menu.counters} moved)"
                )

    # ---------------------------------------------------------------- train
    def _train_cfg(self, tmp: str, needs_pool: bool, use_async: bool) -> Dict:
        return {
            "dataset": {
                "name": "synthetic", "root": tmp, "n_classes": 4,
                "image_size": 16, "n_samples": 256,
            },
            "training": {
                "optimizer": {
                    "name": "SGD", "lr": 0.01, "weight_decay": 1.0e-4,
                    "momentum": 0.9,
                },
                "lr_schedule": {
                    "name": "multi_step", "milestones": [1000], "gamma": 0.1,
                },
                "train_iters": _TRAIN_ITERS,
                "print_interval": 100,
                "val_interval": 10_000,
                "batch_size": 8,
                "num_workers": 1 if needs_pool else 0,
                "worker_mode": "process",
                "sync_bn": False,
                "checkpoint": {
                    "dir": os.path.join(tmp, "ckpt"),
                    "interval": _TRAIN_CKPT_INTERVAL,
                    "resume": True,
                    "retry": {"backoff": 0.01},
                    "async": use_async,
                    "max_inflight": 1,
                },
                "fault_tolerance": {
                    "anomaly": {
                        "enabled": True,
                        "max_consecutive": _ANOMALY_MAX_CONSEC,
                    },
                    "watchdog": {
                        "enabled": True, "min_seconds": 0.5, "factor": 4.0,
                        "poll_seconds": 0.05, "warmup": 3,
                    },
                },
                "integrity": {
                    "enabled": True, "check_interval": 4, "replicas": 3,
                    "max_consecutive": 2,
                },
                "telemetry": {
                    "dir": os.path.join(tmp, "telemetry"),
                    "snapshot_interval": 4,
                },
            },
            "validation": {"batch_size": 8, "num_workers": 0},
            "model": {"name": "ResNet18"},
        }

    def _train_once(self, tmp: str, needs_pool: bool, use_async: bool,
                    spec: Optional[str]) -> Dict:
        from .runner import Runner

        fault.reset_counters()
        injector = fault.install(spec)
        try:
            runner = Runner(
                num_nodes=1, rank=0, seed=3,
                dist_url="tcp://127.0.0.1:9901", dist_backend="tpu",
                multiprocessing=False, logger_queue=None,
                global_cfg=self._train_cfg(tmp, needs_pool, use_async),
                tb_writer_constructor=lambda: None,
            )
            runner()
            digest = self._params_digest(runner.state.params)
            final_iter = runner.iter
            state_step = int(runner.state.step)
        finally:
            fault.install(None)
        tel_dir = os.path.join(tmp, "telemetry")
        snaps = self._read_jsonl(os.path.join(tel_dir, "snapshots.jsonl"))
        spans = self._read_jsonl(os.path.join(tel_dir, "spans_rank0.jsonl"))
        return {
            "injector": injector,
            "counters": dict(fault.counters()),
            "digest": digest,
            "final_iter": final_iter,
            "state_step": state_step,
            "goodput": (snaps[-1].get("goodput") if snaps else None) or {},
            "spans": spans,
        }

    def _train_twin(self, needs_pool: bool, use_async: bool) -> Dict:
        key = ("train", needs_pool, use_async)
        if key not in self._twins:
            with tempfile.TemporaryDirectory(prefix="soak_twin_") as tmp:
                run = self._train_once(tmp, needs_pool, use_async, None)
            self._twins[key] = {
                "digest": run["digest"],
                "final_iter": run["final_iter"],
                "state_step": run["state_step"],
            }
        return self._twins[key]

    def _run_train(self, scn: Scenario, result: Dict,
                   failures: List[str]) -> None:
        from ..telemetry import slo

        kinds = set(scn.kinds())
        needs_pool = "kill_worker" in kinds
        use_async = "ckpt_fail" not in kinds  # sync saves feed ckpt_save
        baseline = self._thread_baseline()
        with tempfile.TemporaryDirectory(prefix="soak_train_") as tmp:
            run = self._train_once(tmp, needs_pool, use_async, scn.spec())
        counters = run["counters"]
        result["counters"] = {k: v for k, v in counters.items() if v}
        self._check_accounting(scn, run["injector"], counters, failures)
        if run["final_iter"] < _TRAIN_ITERS:
            failures.append(
                f"run stopped at iter {run['final_iter']}/{_TRAIN_ITERS}"
            )
        if "nan_batch" in kinds:
            burst = sum(
                1 for e in scn.entries if e.kind == "nan_batch"
            ) >= _ANOMALY_MAX_CONSEC
            if burst and counters.get("rollbacks", 0) < 1:
                failures.append("nan burst injected but no rollback")
        leaked = self._leaked_threads(baseline)
        if leaked:
            failures.append(f"leaked threads: {leaked}")
        gp = run["goodput"]
        ratio = gp.get("goodput_ratio")
        result["goodput_ratio"] = ratio
        if ratio is not None and ratio < self.goodput_floor:
            failures.append(
                f"goodput {ratio:.3f} under floor {self.goodput_floor}"
            )
        result["slo"] = slo.summarize_recoveries(run["spans"])
        if result["slo"]["unrecovered"]:
            failures.append(
                f"{result['slo']['unrecovered']} recovery event(s) with no "
                "productive step after them"
            )
        if scn.parity_expected:
            twin = self._train_twin(needs_pool, use_async)
            same = (
                run["digest"] == twin["digest"]
                and run["state_step"] == twin["state_step"]
            )
            result["parity"] = bool(same)
            if not same:
                failures.append(
                    "bit-parity vs uninjected twin violated "
                    f"(step {run['state_step']} vs {twin['state_step']})"
                )

    # ---------------------------------------------------------------- serve
    _SERVE_PROMPT_LENS = (2, 6, 4, 5)
    _SERVE_VOCAB = 61

    def _serve_model(self):
        if not hasattr(self, "_lm"):
            import jax
            import jax.numpy as jnp

            from ..models.transformer_lm import TransformerLM

            model = TransformerLM(
                vocab_size=self._SERVE_VOCAB, max_len=32, embed_dim=32,
                depth=2, num_heads=4,
            )
            params = model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
            )["params"]
            self._lm = (model, params)
        return self._lm

    def _serve_once(self, spec: Optional[str]) -> Dict:
        """Drive one scheduler through prefill, a few checked ticks, and a
        deadline-bounded drain — injected faults land mid-drive AND
        mid-drain (the compound-#3 window)."""
        import numpy as np

        from ..serving.scheduler import ContinuousScheduler

        model, params = self._serve_model()
        rng = np.random.default_rng(7)
        prompts = [
            rng.integers(2, self._SERVE_VOCAB, ln).astype(np.int32)
            for ln in self._SERVE_PROMPT_LENS
        ]
        fault.reset_counters()
        injector = fault.install(spec)
        try:
            sched = ContinuousScheduler(
                model, params,
                slots=4, block_size=4, num_blocks=16,
                batch_buckets=[4], seq_buckets=[8], max_new_tokens=6,
                temperature=0.0, eos_id=None, prefix_cache=False,
                start=False,
                resilience={
                    "max_restarts": 4,
                    "poison_bisect": True,
                    "drain_deadline_ms": 120_000,
                    "watchdog": {
                        "enabled": True, "min_seconds": 0.15, "factor": 4.0,
                        "warmup": 3, "poll_seconds": 0.02,
                    },
                },
            )
            futs = [sched.submit(p) for p in prompts]
            # a few hand-driven ticks with per-tick pool invariants, then
            # the remaining faults fire inside the drain window
            for _ in range(3):
                sched.tick()
                sched._kv.check_invariants()
            drain_ms = sched.drain(deadline_ms=120_000)
            results = []
            for f in futs:
                try:
                    results.append(tuple(int(t) for t in
                                         f.result(timeout=60)["tokens"]))
                except Exception as e:  # poisoned futures carry diagnosis
                    results.append(f"{type(e).__name__}")
            sched._kv.check_invariants()
            metrics = sched.metrics.snapshot()
        finally:
            fault.install(None)
        from ..telemetry.spans import get_recorder

        return {
            "injector": injector,
            "counters": dict(fault.counters()),
            "metrics": metrics,
            "results": results,
            "drain_ms": drain_ms,
            "blocks_in_use": sched._kv.blocks_in_use,
            "spans": get_recorder().recent(None),
        }

    def _serve_twin(self) -> Dict:
        key = ("serve",)
        if key not in self._twins:
            run = self._serve_once(None)
            self._twins[key] = {"results": run["results"]}
        return self._twins[key]

    def _run_serve(self, scn: Scenario, result: Dict,
                   failures: List[str]) -> None:
        from ..telemetry import slo
        from ..telemetry.spans import SpanRecorder, set_recorder

        baseline = self._thread_baseline()
        twin = self._serve_twin()
        set_recorder(SpanRecorder(ring=2048))  # fresh ring for MTTR spans
        try:
            run = self._serve_once(scn.spec())
        finally:
            set_recorder(None)
        tallies = dict(run["counters"])
        # single-engine serve: the flat serving_* mirror carries the
        # scheduler counters the menu attributes against
        for name, v in run["metrics"].items():
            tallies.setdefault(name, v if isinstance(v, int) else 0)
        result["counters"] = {
            k: v for k, v in tallies.items()
            if v and isinstance(v, int)
        }
        self._check_accounting(scn, run["injector"], tallies, failures)
        leaked = self._leaked_threads(baseline)
        if leaked:
            failures.append(f"leaked threads: {leaked}")
        if run["blocks_in_use"] != 0:
            failures.append(
                f"{run['blocks_in_use']} KV blocks still allocated after "
                "drain"
            )
        n_poison = sum(
            1 for e in scn.entries if e.kind in ("serve_raise", "serve_nan")
        )
        poisoned = [
            i for i, r in enumerate(run["results"]) if isinstance(r, str)
        ]
        if tallies.get("requests_poisoned", 0) != n_poison:
            failures.append(
                f"poison attribution: {n_poison} poison fault(s) injected "
                f"but requests_poisoned={tallies.get('requests_poisoned', 0)}"
            )
        # parity oracle: every request the scenario did not poison must
        # complete token-identical to the uninjected twin
        for i, (got, want) in enumerate(zip(run["results"],
                                            twin["results"])):
            if i in poisoned:
                continue
            if got != want:
                failures.append(
                    f"request {i} tokens diverged from twin after recovery"
                )
        result["parity"] = not any(
            f.startswith("request") for f in failures
        )
        result["drain_ms"] = round(run["drain_ms"], 1)
        result["slo"] = slo.summarize_recoveries(run["spans"])
        want_recovery = (
            {"serve_device_lost", "serve_hang"} & set(scn.kinds())
        )
        if want_recovery and result["slo"]["recoveries"] < 1:
            failures.append(
                f"{sorted(want_recovery)} injected but no serving_restart "
                "recovery span observed"
            )

    # -------------------------------------------------------------- elastic
    def _run_elastic(self, scn: Scenario, result: Dict,
                     failures: List[str]) -> None:
        """kill_peer under load: 2 multihost_worker processes, the victim
        rank SIGKILLs itself mid-run, the survivor must DIAGNOSE the loss
        (PeerLostError + emergency save) and exit 0 — compound-#1's
        process-level soak.

        Per-rank fault specs follow tests/test_elastic.py's chaos idiom:
        the victim gets the ``kill_peer`` entry, the survivor swaps it for
        a 2.5s stall at the SAME step so the death is strictly older than
        the heartbeat timeout when the survivor's pre-step liveness check
        runs (otherwise a short run can finish before staleness trips).
        Skipped (not failed) when this JAX's CPU backend cannot run
        multi-process computations at all — the same platform limit the
        tier-1 elastic test skips on.
        """
        tests_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)
            ))), "tests",
        )
        worker = os.path.join(tests_dir, "multihost_worker.py")
        if not os.path.exists(worker):
            failures.append(f"multihost worker missing: {worker}")
            return
        kill = next(e for e in scn.entries if e.kind == "kill_peer")
        victim = int(kill.arg or 0)
        shared = [e for e in scn.entries if e.kind != "kill_peer"]
        specs = {
            victim: ";".join(
                [e.render() for e in shared] + [f"kill_peer@{kill.step}"]
            ),
            1 - victim: ";".join(
                [e.render() for e in shared]
                + [f"stall_step@{kill.step}:2.5"]
            ),
        }
        with tempfile.TemporaryDirectory(prefix="soak_elastic_") as tmp:
            port_file = os.path.join(tmp, "port")
            outs = [os.path.join(tmp, f"out{r}.json") for r in range(2)]
            procs = []
            for r in range(2):
                env = dict(os.environ)
                env.pop("XLA_FLAGS", None)
                env.pop("JAX_PLATFORMS", None)
                env.update({
                    "MH_RANK": str(r), "MH_NUM_NODES": "2",
                    "MH_PORT": "29870,29871,29872,29873",
                    "MH_PORT_FILE": port_file,
                    "MH_OUT": outs[r], "MH_LOCAL_DEVICES": "2",
                    "MH_ELASTIC": "1", "MH_TRAIN_ITERS": "10",
                    "MH_HB_INTERVAL": "0.1", "MH_HB_TIMEOUT": "0.75",
                    "MH_CKPT_DIR": os.path.join(tmp, "ckpt"),
                    "MH_CKPT_INTERVAL": "3",
                    fault.ENV_VAR: specs[r],
                })
                log = open(os.path.join(tmp, f"rank{r}.log"), "w")
                procs.append((subprocess.Popen(
                    [sys.executable, worker], env=env,
                    stdout=log, stderr=subprocess.STDOUT,
                ), log))
            deadline = time.monotonic() + 300
            logs = []
            for p, log in procs:
                try:
                    p.wait(timeout=max(1.0, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
                log.close()
                with open(log.name) as fp:
                    logs.append(fp.read())
            if any(
                "Multiprocess computations aren't implemented" in lg
                for lg in logs
            ):
                result["skipped"] = (
                    "this JAX's CPU backend cannot run multi-process "
                    "computations (needs the grafted toolchain or a real "
                    "accelerator)"
                )
                return
            survivor = None
            if os.path.exists(outs[1 - victim]):
                with open(outs[1 - victim]) as fp:
                    rec = json.load(fp)
                if rec.get("peer_lost"):
                    survivor = rec
            if survivor is None:
                failures.append(
                    "the surviving rank did not diagnose the peer loss "
                    f"(exit codes {[p.returncode for p, _ in procs]})"
                )
                return
            counters = survivor.get("counters", {})
            result["counters"] = counters
            result["survivor_rank"] = survivor["rank"]
            if counters.get("peer_lost", 0) < 1:
                failures.append("survivor did not count peer_lost")
            if counters.get("elastic_saves", 0) < 1:
                failures.append(
                    "survivor diagnosed the loss but wrote no emergency "
                    "checkpoint"
                )
            if "ckpt_fail" in scn.kinds() and counters.get(
                "ckpt_retries", 0
            ) < 1:
                failures.append("injected ckpt_fail was never retried")

    # ---------------------------------------------------------------- fleet
    def _run_fleet(self, scn: Scenario, result: Dict,
                   failures: List[str]) -> None:
        """replica_down/replica_hang against a 2-replica fleet: every
        request must complete token-identical to an unkilled twin."""
        import copy

        import numpy as np

        from ..config_parsing import get_serve_cfg
        from ..serving import ServingFleet

        base = get_serve_cfg(
            os.environ.get("BENCH_SERVE_CONFIG", "config/serve-lm.yml")
        )
        base["serving"]["scheduler"] = {
            "enabled": True, "slots": 4, "block_size": 4, "num_blocks": 64,
            "prefix_cache": True,
        }
        base["serving"]["resilience"] = {
            "max_restarts": 3, "poison_bisect": True,
            "drain_deadline_ms": 60_000,
        }
        has_hang = "replica_hang" in scn.kinds()
        if has_hang:
            # fast heartbeats + hedging so the wedge is DETECTED, not
            # merely waited out.  The staleness clock must sit ABOVE the
            # longest legitimate scheduler-loop stall (a fresh bucket or
            # batch-size compile blocks the loop for seconds, silencing
            # heartbeats exactly like the wedge) and BELOW the injected
            # hang, which _place_fleet makes 6.5-8s for that reason.
            base["serving"]["fleet"] = {
                "replicas": 2, "affinity": True, "hedge_ms": 250.0,
                "heartbeat_interval_s": 0.2, "heartbeat_timeout_s": 5.0,
                "poll_interval_s": 0.02,
            }
        else:
            base["serving"]["fleet"] = {
                "replicas": 2, "affinity": True,
                "heartbeat_timeout_s": 30.0, "poll_interval_s": 0.02,
            }

        def run_fleet(inject: bool):
            cfg = copy.deepcopy(base)
            cfg["serving"]["temperature"] = 0.0
            rng = np.random.default_rng(0)
            vocab = cfg["dataset"]["n_classes"]
            fault.reset_counters()
            fleet = ServingFleet.from_config(cfg)
            try:
                seq_max = fleet.replicas[0].seq_buckets[-1]
                for rep in fleet.replicas:  # compile outside chaos window
                    rep.submit(
                        rng.integers(2, vocab, seq_max // 2).astype(np.int32)
                    ).result(timeout=600)
                if inject:
                    # fleet fault steps count router polls / replica ticks
                    # from NOW: offset past the warmup's consumption
                    poll0 = fleet.router._poll_no
                    tick0 = max(
                        r.scheduler._tick_no for r in fleet.replicas
                    )
                    shifted = ";".join(
                        FaultEntry(
                            e.kind,
                            e.step + (
                                tick0 if e.kind.startswith("serve_")
                                else poll0
                            ),
                            e.arg,
                        ).render()
                        for e in scn.entries
                    )
                    fault.install(shifted)
                mnt = min(4, fleet.replicas[0].max_new_tokens)
                futures = []
                for i in range(8):
                    ln = int(rng.integers(1, seq_max + 1))
                    prompt = rng.integers(2, vocab, ln).astype(np.int32)
                    futures.append(fleet.submit(prompt, max_new_tokens=mnt))
                streams = [
                    tuple(int(t) for t in f.result(timeout=600)["tokens"])
                    for f in futures
                ]
                pend = fault.get_injector().pending()
                return streams, dict(fault.counters()), pend
            finally:
                fault.install(None)
                fleet.close()

        baseline = self._thread_baseline()
        twin_key = ("fleet", "replica_hang" in scn.kinds())
        if twin_key not in self._twins:
            streams, _, _ = run_fleet(inject=False)
            self._twins[twin_key] = {"results": streams}
        twin = self._twins[twin_key]
        streams, counters, pend = run_fleet(inject=True)
        result["counters"] = {k: v for k, v in counters.items() if v}
        if pend:
            failures.append(f"faults never fired: {pend}")
        if streams != twin["results"]:
            failures.append("fleet token streams diverged from unkilled twin")
        result["parity"] = streams == twin["results"]
        for kind in scn.kinds():
            menu = FAULT_MENU[kind]
            if kind.startswith("serve_"):
                # per-replica mirrors carry serve counters in fleet mode
                moved = any(
                    counters.get(f"serving_r{r}_{c}", 0) > 0
                    for r in range(2) for c in ("engine_restarts",)
                ) if kind == "serve_device_lost" else True
            else:
                moved = any(counters.get(c, 0) > 0 for c in menu.counters)
            if not moved:
                failures.append(
                    f"{kind}: no recovery attribution in fleet counters"
                )
        leaked = self._leaked_threads(baseline)
        if leaked:
            failures.append(f"leaked threads: {leaked}")

    # -------------------------------------------------------------- scaling
    def _run_scaling(self, scn: Scenario, result: Dict,
                     failures: List[str]) -> None:
        """Faults landing INSIDE autoscaler scaling events.

        A 1-replica fleet under a FleetAutoscaler rides a synthetic flash
        crowd through three windows, installing each phase's faults only
        when its window opens (stage-wise installs — a later window's
        fault must not fire early against the warmup's polls):

        - *decision*: ``autoscale_hang`` wedges the scale-up poll itself;
          the contract is that the post-hang decision runs on FRESHLY
          re-read signals, so the crowd may simply re-pressure the next
          poll — the run keeps submitting until capacity arrives.
        - *scale-up*: ``replica_down`` kills the replica the scale-up
          just added, mid-crowd; the router must fail its in-flight work
          over with token-identical replay and the autoscaler must
          re-grow capacity.
        - *drain*: ``serve_nan``/``serve_raise`` (SDC / poison) land
          while the scale-down drain is running requests to completion.

        Parity oracle: temperature 0 makes every stream a pure function
        of its prompt, so each unpoisoned request is checked against a
        clean 1-replica reference fleet replaying the same prompts —
        placement-, scale-, and failover-independent by construction.
        """
        import copy

        import numpy as np

        from ..config_parsing import get_serve_cfg
        from ..serving import ServingFleet
        from ..serving.autoscaler import FleetAutoscaler

        base = get_serve_cfg(
            os.environ.get("BENCH_SERVE_CONFIG", "config/serve-lm.yml")
        )
        base["serving"]["scheduler"] = {
            "enabled": True, "slots": 4, "block_size": 4, "num_blocks": 64,
            "prefix_cache": True,
        }
        base["serving"]["resilience"] = {
            "max_restarts": 3, "poison_bisect": True,
            "drain_deadline_ms": 60_000,
        }
        base["serving"]["fleet"] = {
            "replicas": 1, "affinity": True,
            "heartbeat_timeout_s": 30.0, "poll_interval_s": 0.02,
        }
        base["serving"]["temperature"] = 0.0
        # thresholds shaped for the soak's burst arithmetic: an 8-request
        # flash crowd clears backlog_high; a 4-request trickle sits under
        # backlog_low, and occupancy_low=1.0 admits a scale-down WITH
        # requests still decoding — which is the whole point of the drain
        # window (real deployments would set occupancy_low well below 1)
        autoscale_cfg = {
            "min_replicas": 1, "max_replicas": 2,
            "backlog_high": 7, "backlog_low": 6,
            "occupancy_high": 1.5, "occupancy_low": 1.0,
            "scale_up_cooldown_s": 0.0, "scale_down_cooldown_s": 0.0,
            "drain_deadline_ms": 60_000,
        }
        phases: Dict[str, List[FaultEntry]] = {
            "up": [], "decision": [], "drain": [],
        }
        for atom, entry in zip(scn.template, scn.entries):
            phases[atom.partition(":")[0]].append(entry)

        vocab = base["dataset"]["n_classes"]
        fault.reset_counters()
        baseline = self._thread_baseline()
        rng = np.random.default_rng(0)
        cfg = copy.deepcopy(base)
        fleet = ServingFleet.from_config(cfg)
        asc = FleetAutoscaler(fleet, dict(autoscale_cfg))
        stage_leaks: List[str] = []

        def install_stage(entries: List[FaultEntry], offset_of) -> None:
            """Swap the injector to this window's faults; the previous
            window must have fully fired (a pending fault would be
            silently discarded by the swap — that is a failure)."""
            left = fault.get_injector().pending()
            if left:
                stage_leaks.extend(left)
            fault.install(";".join(
                FaultEntry(e.kind, e.step + offset_of(e.kind), e.arg).render()
                for e in entries
            ) or None)

        try:
            seq_max = fleet.replicas[0].seq_buckets[-1]
            mnt = min(4, fleet.replicas[0].max_new_tokens)
            warm = rng.integers(2, vocab, seq_max // 2).astype(np.int32)
            fleet.replicas[0].submit(warm).result(timeout=600)

            submitted: List = []  # (prompt, future)

            def burst(k: int) -> None:
                for _ in range(k):
                    ln = int(rng.integers(1, seq_max + 1))
                    prompt = rng.integers(2, vocab, ln).astype(np.int32)
                    submitted.append(
                        (prompt, fleet.submit(prompt, max_new_tokens=mnt)))

            def pressure_up(tag: str) -> None:
                """Flash-crowd until the autoscaler adds capacity (the
                decision hang may legitimately defer it a round: fresh
                post-hang signals saw the first burst already absorbed)."""
                for _ in range(4):
                    if fleet.live_replicas() >= 2:
                        return
                    burst(8)
                    asc.poll()
                if fleet.live_replicas() < 2:
                    failures.append(f"{tag}: autoscaler never scaled up")

            # ---- window 1: decision (+ the scale-up it wedges)
            install_stage(phases["decision"], lambda k: 0)
            pressure_up("decision window")
            new_idx = max(fleet.router.live_indices())
            if new_idx > 0:  # warm the fresh replica outside fault windows
                fleet.replicas[new_idx].submit(warm).result(timeout=600)

            # ---- window 2: replica death mid-crowd, post-scale-up
            if phases["up"]:
                burst(4)  # the crowd keeps arriving; some land on the
                # new replica — these are the streams the kill must not
                # corrupt
                poll0 = fleet.router._poll_no
                install_stage(phases["up"], lambda k: poll0)
                for _, f in submitted:  # failover completes them
                    f.result(timeout=600)
                # capacity healing: the crowd is still the sizing signal
                pressure_up("post-kill heal")

            for _, f in submitted:
                f.result(timeout=600)

            # ---- window 3: scale-down drain with work in flight
            n_before = len(submitted)
            burst(4)
            # drain faults are tick-keyed on the replica the scale-down
            # will retire (the highest live index — pick_retire_candidate
            # is LIFO): it is the scheduler that ticks through the drain
            # window, so ITS counter is the one that reaches the step
            retiree = fleet.pick_retire_candidate()
            tick0 = fleet.replicas[retiree].scheduler._tick_no
            install_stage(phases["drain"],
                          lambda k: tick0 if k.startswith("serve_") else 0)
            decision = asc.poll()  # blocks through the retiree's drain
            if decision != "down":
                failures.append(
                    f"scale-down poll decided {decision!r}, not 'down'"
                )
            results: List = []
            for prompt, f in submitted:
                try:
                    results.append(
                        (prompt,
                         tuple(int(t) for t in f.result(timeout=600)["tokens"]))
                    )
                except Exception as e:
                    results.append((prompt, type(e).__name__))
            install_stage([], lambda k: 0)  # surface window-3 leftovers
            fired = dict(fault.counters())
        finally:
            fault.install(None)
            fleet.close()

        result["counters"] = {k: v for k, v in fired.items() if v}
        result["scale_ups"] = asc.scale_ups
        result["scale_downs"] = asc.scale_downs
        if stage_leaks:
            failures.append(f"faults never fired: {sorted(stage_leaks)}")
        if asc.scale_ups < 1 or asc.scale_downs < 1:
            failures.append(
                f"scaling events missing: {asc.scale_ups} up(s), "
                f"{asc.scale_downs} down(s)"
            )
        # recovery attribution per kind (fleet mode mirrors serve
        # counters per replica)
        n_reps = len(fleet.replicas)
        for kind in scn.kinds():
            menu = FAULT_MENU[kind]
            moved = any(fired.get(c, 0) > 0 for c in menu.counters)
            if not moved and kind.startswith("serve_"):
                moved = any(
                    fired.get(f"serving_r{r}_{c}", 0) > 0
                    for r in range(n_reps) for c in menu.counters
                )
            if not moved:
                failures.append(
                    f"{kind}: no recovery attribution (none of "
                    f"{menu.counters} moved)"
                )
        # poison accounting: each injected poison fault costs exactly one
        # request; everything else must have completed
        n_poison = sum(
            1 for e in scn.entries if e.kind in ("serve_nan", "serve_raise")
        )
        poisoned = [i for i, (_, r) in enumerate(results)
                    if isinstance(r, str)]
        if len(poisoned) != n_poison:
            failures.append(
                f"poison attribution: {n_poison} poison fault(s) injected "
                f"but {len(poisoned)} request(s) failed "
                f"({[results[i][1] for i in poisoned]})"
            )
        if poisoned and min(poisoned) < n_before:
            failures.append(
                "a pre-drain-window request was poisoned (drain faults "
                "leaked backwards)"
            )
        # parity: greedy streams depend only on the prompt — replay every
        # unpoisoned prompt through a clean static reference fleet
        ref_cache = self._twins.setdefault(("scaling_ref",), {})
        missing = [
            tuple(int(t) for t in p)
            for i, (p, r) in enumerate(results)
            if i not in set(poisoned)
            and (tuple(int(t) for t in p), mnt) not in ref_cache
        ]
        if missing:
            ref_fleet = ServingFleet.from_config(copy.deepcopy(base))
            try:
                ref_fleet.replicas[0].submit(warm).result(timeout=600)
                futs = [
                    (p, ref_fleet.submit(
                        np.asarray(p, np.int32), max_new_tokens=mnt))
                    for p in dict.fromkeys(missing)
                ]
                for p, f in futs:
                    ref_cache[(p, mnt)] = tuple(
                        int(t) for t in f.result(timeout=600)["tokens"])
            finally:
                ref_fleet.close()
        diverged = 0
        for i, (p, r) in enumerate(results):
            if i in set(poisoned):
                continue
            want = ref_cache[(tuple(int(t) for t in p), mnt)]
            if r != want:
                diverged += 1
                failures.append(
                    f"request {i} tokens diverged from reference after "
                    "scaling"
                )
        result["parity"] = diverged == 0
        result["requests"] = len(results)
        leaked = self._leaked_threads(baseline)
        if leaked:
            failures.append(f"leaked threads: {leaked}")

    # --------------------------------------------------------------- disagg
    def _run_disagg(self, scn: Scenario, result: Dict,
                    failures: List[str]) -> None:
        """Faults on the prefill/decode disaggregation transfer edge.

        A 2-replica decode fleet behind a :class:`DisaggFleet` with 2
        prefill replicas serves 2 rounds x 4 prefix groups (same first
        block per group, fresh suffix per round).  One transfer worker +
        single-flight staging serialize the coordinator, so KV-transfer
        ordinal K is exactly the Kth staged request and _place_disagg's
        ordinal-keyed faults land deterministically: round 1 walks
        ordinals 1-4 (all prefill-sourced — the directory starts empty),
        round 2 re-transfers only the groups whose round-1 transfer
        degraded.

        Oracles: every armed fault fires; all 8 streams match the
        uninjected twin bit-for-bit (a transferred block that differed
        from local recompute would break parity by construction); each
        kind's recovery rung moved its FAULT_MENU counter; live KV pools
        hold their invariants; no owned thread outlives close.
        """
        import copy

        import numpy as np

        from ..config_parsing import get_serve_cfg
        from ..serving.disagg import DisaggFleet

        base = get_serve_cfg(
            os.environ.get("BENCH_SERVE_CONFIG", "config/serve-lm.yml")
        )
        base["serving"]["scheduler"] = {
            "enabled": True, "slots": 4, "block_size": 4, "num_blocks": 64,
            "prefix_cache": True,
        }
        base["serving"]["resilience"] = {
            "max_restarts": 3, "poison_bisect": True,
            "drain_deadline_ms": 60_000,
        }
        base["serving"]["fleet"] = {
            "replicas": 2, "affinity": True,
            "heartbeat_timeout_s": 30.0, "poll_interval_s": 0.02,
        }
        # deadline sits above the first import's one-off scatter compile
        # (~100 ms) and decisively below _place_disagg's 1.5-2.0 s stall;
        # 2 prefill replicas so a prefill kill at ordinal 1 leaves
        # capacity for the later ordinals' faults to reach
        base["serving"]["disagg"] = {
            "enabled": True, "prefill_replicas": 2,
            "transfer_deadline_ms": 800.0, "transfer_workers": 1,
        }

        def run_disagg(inject: bool):
            cfg = copy.deepcopy(base)
            cfg["serving"]["temperature"] = 0.0
            rng = np.random.default_rng(0)
            vocab = cfg["dataset"]["n_classes"]
            fault.reset_counters()
            fleet = DisaggFleet.from_config(cfg)
            try:
                seq_max = fleet.fleet.replicas[0].seq_buckets[-1]
                warm_reps = fleet.fleet.replicas + fleet.prefill_replicas
                for rep in warm_reps:  # compile outside the chaos window
                    rep.submit(
                        rng.integers(2, vocab, seq_max // 2).astype(np.int32)
                    ).result(timeout=600)
                # 4 prefix groups: fixed first block, variable suffix
                blocks = [
                    rng.integers(2, vocab, 4).astype(np.int32)
                    for _ in range(4)
                ]
                if inject:
                    # transfer ordinals count coordinator transfers from
                    # NOW (the direct warms above bypassed it — clock at
                    # 0); only the handoff replica_down rides the router
                    # poll clock and shifts past the warmup's polls
                    poll0 = fleet.router._poll_no
                    shifted = ";".join(
                        FaultEntry(
                            e.kind,
                            e.step + (
                                poll0 if e.kind == "replica_down" else 0
                            ),
                            e.arg,
                        ).render()
                        for e in scn.entries
                    )
                    fault.install(shifted)
                mnt = min(4, fleet.fleet.replicas[0].max_new_tokens)
                streams = []
                for _round in range(2):
                    futures = []
                    for blk in blocks:
                        ln = int(rng.integers(1, seq_max - 4 + 1))
                        prompt = np.concatenate(
                            [blk, rng.integers(2, vocab, ln).astype(np.int32)]
                        )
                        futures.append(
                            fleet.submit(prompt, max_new_tokens=mnt)
                        )
                    # round barrier: every stage preceded its submit on
                    # the single worker, so round 2 sees round 1's
                    # directory outcome, not a half-staged one
                    streams.extend(
                        tuple(int(t) for t in f.result(timeout=600)["tokens"])
                        for f in futures
                    )
                pend = fault.get_injector().pending()
                for rep in warm_reps:
                    sched = rep.scheduler
                    if not (sched._closed or sched._dead):
                        sched._kv.check_invariants()
                return streams, dict(fault.counters()), pend
            finally:
                fault.install(None)
                fleet.close()

        baseline = self._thread_baseline()
        twin_key = ("disagg",)
        if twin_key not in self._twins:
            streams, _, _ = run_disagg(inject=False)
            self._twins[twin_key] = {"results": streams}
        twin = self._twins[twin_key]
        streams, counters, pend = run_disagg(inject=True)
        result["counters"] = {k: v for k, v in counters.items() if v}
        if pend:
            failures.append(f"faults never fired: {pend}")
        if streams != twin["results"]:
            failures.append(
                "disagg token streams diverged from uninjected twin"
            )
        result["parity"] = streams == twin["results"]
        for kind in scn.kinds():
            menu = FAULT_MENU[kind]
            if not any(counters.get(c, 0) > 0 for c in menu.counters):
                failures.append(
                    f"{kind}: no recovery attribution in disagg counters"
                )
        leaked = self._leaked_threads(baseline)
        if leaked:
            failures.append(f"leaked threads: {leaked}")

    # ------------------------------------------------------------------ run
    def run_scenario(self, scn: Scenario) -> Dict:
        t0 = time.monotonic()
        failures: List[str] = []
        result: Dict = {
            "index": scn.index,
            "family": scn.family,
            "overlap": scn.overlap,
            "spec": scn.spec(),
        }
        runner = {
            "train": self._run_train,
            "serve": self._run_serve,
            "elastic": self._run_elastic,
            "fleet": self._run_fleet,
            "scaling": self._run_scaling,
            "disagg": self._run_disagg,
        }[scn.family]
        try:
            runner(scn, result, failures)
        except Exception as e:  # a crashed scenario is a finding, not a halt
            self.logger.exception("scenario %d crashed", scn.index)
            failures.append(f"crashed: {type(e).__name__}: {e}")
        result["ok"] = not failures
        result["failures"] = failures
        result["duration_s"] = round(time.monotonic() - t0, 2)
        return result

    def run(self, n: int = 20) -> Dict:
        """The soak: ``n`` scenarios, oracles on each, one summary dict."""
        scenarios = self.generator.generate(n)
        results = []
        for scn in scenarios:
            self.logger.info(
                "soak scenario %d/%d [%s/%s]: %s",
                scn.index + 1, n, scn.family, scn.overlap, scn.spec(),
            )
            results.append(self.run_scenario(scn))
        kinds = sorted({k for s in scenarios for k in s.kinds()})
        mttrs = [
            e["mttr_ms"]
            for r in results
            for e in (r.get("slo") or {}).get("events", ())
            if e["mttr_ms"] is not None
        ]
        return {
            "seed": self.generator.seed,
            "families": list(self.generator.families),
            "scenarios": n,
            "passed": sum(
                1 for r in results if r["ok"] and "skipped" not in r
            ),
            "failed": sum(1 for r in results if not r["ok"]),
            "skipped": sum(1 for r in results if "skipped" in r),
            "kinds_exercised": kinds,
            "kinds_uncovered": uncovered_kinds(),
            "mttr_ms_max": max(mttrs) if mttrs else None,
            "mttr_ms_mean": (
                round(sum(mttrs) / len(mttrs), 1) if mttrs else None
            ),
            "goodput_floor": self.goodput_floor,
            "coverage": coverage_matrix(),
            "results": results,
        }
