"""Marker-convention fixture BODY: copied into a tmp tests dir under a
``test_*.py`` name by test_static_analysis.py (stored here under a
non-test name so pytest never collects the seeded violations)."""
import time

import pytest


def test_unmarked_fault_chaos():
    from pytorch_distributed_training_tpu.engine.watchdog import StepWatchdog

    wd = StepWatchdog(min_seconds=0.05)
    time.sleep(0.2)
    wd.close()


@pytest.mark.chaos
def test_properly_marked_fault_chaos():
    from pytorch_distributed_training_tpu.engine.watchdog import StepWatchdog

    wd = StepWatchdog(min_seconds=0.05)
    time.sleep(0.2)
    wd.close()
