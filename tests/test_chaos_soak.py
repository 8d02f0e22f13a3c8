"""Chaos soak engine (engine/chaos.py): schedule determinism, the fault
coverage matrix, scenario well-formedness, and oracle-judged soak runs.

The heavy proof is the slow-marked 20-scenario soak at the end of this
file; tier-1 pins the properties that make it
trustworthy and replayable:

  - the scenario schedule is a pure function of the seed — a red soak
    rerun with the same seed replays byte-identical fault specs;
  - every fault kind fault.py can inject appears in FAULT_MENU AND in at
    least one generator template — registering a new kind without soak
    coverage fails here, not silently in production;
  - every generated spec parses through the real injector grammar;
  - a small seeded soak (serve family — no subprocesses, no multi-second
    stalls) runs green end to end through the real scheduler with the
    parity/accounting/SLO oracles armed.
"""
import json

import pytest

from pytorch_distributed_training_tpu.engine import fault
from pytorch_distributed_training_tpu.engine.chaos import (
    FAMILIES,
    FAULT_MENU,
    OVERLAP_MODES,
    ChaosSoakEngine,
    ScenarioGenerator,
    coverage_matrix,
    disagg_cells,
    registered_fault_kinds,
    scaling_cells,
    uncovered_kinds,
)


@pytest.fixture(autouse=True)
def _fault_hygiene():
    fault.install(None)
    fault.reset_counters()
    yield
    fault.install(None)
    fault.reset_counters()


# --------------------------------------------------------------------- #
# schedule determinism


def test_schedule_is_a_pure_function_of_the_seed():
    a = ScenarioGenerator(7, families=FAMILIES).schedule_json(20)
    b = ScenarioGenerator(7, families=FAMILIES).schedule_json(20)
    assert a == b  # byte-identical, not merely equivalent
    assert ScenarioGenerator(8, families=FAMILIES).schedule_json(20) != a


def test_generator_is_reusable_without_drift():
    """generate() must not mutate generator state: calling twice on ONE
    instance yields the same schedule (fresh Random(seed) per call)."""
    g = ScenarioGenerator(11, families=("train", "serve"))
    assert g.schedule_json(6) == g.schedule_json(6)


def test_schedule_prefix_stability():
    """The first k scenarios of an n-scenario schedule equal a k-scenario
    schedule: growing a soak never reshuffles already-run scenarios."""
    g = ScenarioGenerator(5, families=FAMILIES)
    long = json.loads(g.schedule_json(12))
    short = json.loads(g.schedule_json(4))
    assert long[:4] == short


# --------------------------------------------------------------------- #
# coverage matrix


def test_fault_menu_matches_registered_kinds_exactly():
    """FAULT_MENU is pinned against fault.py's registries both ways: a
    kind added to fault.py without a menu entry (or vice versa) fails."""
    assert sorted(FAULT_MENU) == list(registered_fault_kinds())
    matrix = coverage_matrix()
    assert sorted(matrix) == sorted(FAULT_MENU)
    for kind, row in matrix.items():
        assert row["family"] in FAMILIES, kind
        assert row["recovery"], f"{kind}: empty recovery path"


def test_every_registered_kind_has_template_coverage():
    """No registered fault kind may be absent from the scenario space."""
    assert uncovered_kinds() == []


def test_scaling_cells_cover_scale_up_drain_and_decision():
    """ISSUE 18 acceptance: the coverage matrix gains SCALING-EVENT
    cells — faults during scale-up, during scale-down drain, and at
    autoscaler decision time — each populated from the scaling-family
    templates, so killing a template empties a cell and fails here."""
    assert "scaling" in FAMILIES
    cells = scaling_cells()
    assert set(cells) == {"scale_up", "drain", "decision"}
    assert "replica_down" in cells["scale_up"]
    assert set(cells["drain"]) >= {"serve_nan", "serve_raise"}
    assert cells["decision"] == ["autoscale_hang"]
    # the decision-time kind is a first-class registered fault, not a
    # harness hack: it appears in the menu AND the injector grammar
    assert "autoscale_hang" in FAULT_MENU
    assert "autoscale_hang" in registered_fault_kinds()


def test_disagg_cells_cover_transfer_and_handoff():
    """ISSUE 19 acceptance: the coverage matrix gains KV-TRANSFER cells
    — faults on the prefill->decode transfer edge and decode death
    mid-handoff — each populated from the disagg-family templates, so
    killing a template empties a cell and fails here."""
    assert "disagg" in FAMILIES
    cells = disagg_cells()
    assert set(cells) == {"transfer", "handoff"}
    assert set(cells["transfer"]) == {
        "kv_transfer_stall", "kv_transfer_corrupt", "prefill_replica_down"
    }
    assert cells["handoff"] == ["replica_down"]
    # the transfer kinds are first-class registered faults, not harness
    # hacks: they appear in the menu AND the injector grammar
    for kind in cells["transfer"]:
        assert kind in FAULT_MENU
        assert kind in registered_fault_kinds()


def test_uncovered_kinds_detects_a_coverage_gap(monkeypatch):
    """The matrix check is live, not vacuous: registering a new kind in
    fault.py without adding soak coverage is reported."""
    from pytorch_distributed_training_tpu.engine import chaos

    monkeypatch.setattr(
        chaos, "registered_fault_kinds",
        lambda: tuple(sorted(set(registered_fault_kinds()) | {"new_kind"})),
    )
    assert chaos.uncovered_kinds() == ["new_kind"]


# --------------------------------------------------------------------- #
# scenario well-formedness


def test_generated_scenarios_compose_and_parse():
    scenarios = ScenarioGenerator(42, families=FAMILIES).generate(24)
    assert len(scenarios) == 24
    for i, scn in enumerate(scenarios):
        assert scn.index == i
        assert scn.family == FAMILIES[i % len(FAMILIES)]  # round-robin
        assert scn.overlap in OVERLAP_MODES
        assert 2 <= len(scn.entries) <= 4
        # every spec must survive the real injector grammar
        inj = fault.FaultInjector(scn.spec())
        assert inj.active
        for kind in scn.kinds():
            assert kind in FAULT_MENU
    # parity expectation is the AND over the menu rows
    for scn in scenarios:
        assert scn.parity_expected == all(
            FAULT_MENU[k].parity for k in scn.kinds()
        )


# --------------------------------------------------------------------- #
# seeded soak runs


@pytest.mark.slow
@pytest.mark.chaos
def test_soak_smoke_serve_family():
    """Two seeded serve-family scenarios through the REAL continuous
    scheduler with all oracles armed: exact poison attribution, token
    parity vs the uninjected twin, kv-pool and thread hygiene."""
    eng = ChaosSoakEngine(seed=42, families=("serve",))
    summary = eng.run(2)
    assert summary["failed"] == 0, [
        r["failures"] for r in summary["results"] if not r["ok"]
    ]
    assert summary["passed"] == 2
    assert summary["kinds_uncovered"] == []
    for r in summary["results"]:
        assert r["family"] == "serve"
        assert r["counters"], "scenario fired nothing"


@pytest.mark.slow
@pytest.mark.chaos
def test_soak_smoke_scaling_family():
    """One seeded scaling scenario end to end: the autoscaler grows the
    fleet into an injected flash crowd, faults land inside the scaling
    events (per the scenario's phase-tagged template), and scale-down
    drains with token parity against clean greedy reference streams."""
    eng = ChaosSoakEngine(seed=3, families=("scaling",))
    summary = eng.run(1)
    assert summary["failed"] == 0, [
        r["failures"] for r in summary["results"] if not r["ok"]
    ]
    assert summary["passed"] == 1
    r = summary["results"][0]
    assert r["family"] == "scaling"
    assert r["scale_ups"] >= 1 and r["scale_downs"] >= 1
    assert r["counters"], "scenario fired nothing"


@pytest.mark.slow
@pytest.mark.chaos
def test_soak_smoke_disagg_family():
    """One seeded disagg scenario end to end: KV blocks stream from a
    prefill replica to the router-chosen decode replica, injected
    transfer faults (stall / corrupt / prefill death / decode handoff
    death) each land on their recovery rung, and all 8 streams match
    the uninjected twin bit for bit."""
    eng = ChaosSoakEngine(seed=3, families=("disagg",))
    summary = eng.run(1)
    assert summary["failed"] == 0, [
        r["failures"] for r in summary["results"] if not r["ok"]
    ]
    assert summary["passed"] == 1
    r = summary["results"][0]
    assert r["family"] == "disagg"
    assert r["parity"] is True
    assert r["counters"].get("serving_disagg_transfers", 0) >= 1


@pytest.mark.slow
@pytest.mark.chaos
def test_soak_twenty_scenarios_in_process_families():
    """The full soak: 20 seeded multi-fault scenarios round-robin over the
    families that run in this process (train + serve + fleet) through the
    real Runner / scheduler / fleet, every one judged by the shared oracles
    and every one green.  The elastic family is left out: its template
    kills rank 0, the coordination service's leader, and JAX's client then
    aborts the survivor before the heartbeat layer can diagnose the loss
    (tests/test_elastic.py proves that ladder with rank 1 as the victim)."""
    eng = ChaosSoakEngine(seed=42, families=("train", "serve", "fleet"))
    summary = eng.run(20)
    assert summary["failed"] == 0, [
        (r["index"], r["family"], r["spec"], r["failures"])
        for r in summary["results"] if not r["ok"]
    ]
    assert summary["passed"] == 20
