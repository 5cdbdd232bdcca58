"""`correct` of cell gru_lm.dense: true on a sound run, false under the control
and under each fault the cell can have (a one-chip cell has no exchange
between chips to leave out)."""

import pytest

from bench import harness
from bench.tests import faults

CELL = "gru_lm.dense"
LIMITS = harness.load_json("workloads", CELL)["check"]["limits"]


def test_sound_run_is_correct():
    line = faults.run_toy(CELL)
    assert line["correct"] is True, line["checks"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_fault_is_not_correct(monkeypatch, fault):
    faults.plant(monkeypatch, fault)
    line = faults.run_toy(CELL)
    assert line["correct"] is False, line["checks"]


def test_control_fails_a_limit():
    r = faults.control_readings(CELL)
    assert any(r[k] > lim for k, lim in LIMITS.items()), r
