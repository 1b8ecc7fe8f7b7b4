"""Each fault a one-chip cell can have, planted in the trainer's timed path
(``faults.py``), turns a tiny run's ``correct`` false."""
import pytest

from faults import ONE_CHIP
from tiny import run_tiny

CELLS = ["sent140-lstm.engine-k64", "din-amazon.step-k128"]


@pytest.mark.parametrize("fault", sorted(ONE_CHIP))
@pytest.mark.parametrize("name", CELLS)
def test_fault_in_the_timed_path_is_not_correct(name, fault, monkeypatch):
    ONE_CHIP[fault](monkeypatch)
    res, _ = run_tiny(name)
    assert not res["correct"], res["checks"]
