"""The selection cell's ``correct`` must refuse what is wrong.

The cell runs as ``bench/run.py`` runs it, past the look for a chip, on the
CPU at a size a test run holds (n=1,024, d=100, k=10, Pallas kernels in
interpret mode):

- as it is, where it must come out correct;
- with its control in the program's place, the plain greedy at
  ``Precision.HIGH`` (three bfloat16 passes) where the configuration states
  float32 at HIGHEST, on three seeds;
- with each fault of ``bench/faults_selection.py`` planted under
  ``run_selection``: the picks of rounds 0 and 1 swapped, the winner never
  folded into the cache, the gain kernel over half of the ground set's
  rows, and the previous selection handed back unchanged.
"""
import io

import jax
import pytest

from bench import faults_selection, run

CELL = "paper_v_a_greedy.selection"
SMALL = dict(n=1024, d=100, k=10, backend="pallas_interpret")
FAULTS = faults_selection.FAULTS["selection"]


def run_small(seed, control=False):
    log = io.StringIO()
    result = run.run_cell(CELL, seed, 1.0, False, need_chip=False,
                          control=control, log=log, overrides=SMALL)
    return result, log.getvalue()


@pytest.fixture
def fresh_programs():
    """Programs traced before a fault was planted would hide it."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_the_cell_uses_the_selection_faults():
    _, _, traffic = run.cell_parts(CELL)
    assert traffic["generator"] in faults_selection.FAULTS


def test_program_is_correct():
    result, log = run_small(2**31 + 11)
    assert result["correct"], log
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"evals_per_s", "setup_s"}


@pytest.mark.parametrize("seed", [5, 6, 2**32 + 7])
def test_control_is_refused(seed):
    result, log = run_small(seed, control=True)
    assert not result["correct"], log


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_refused(fault, fresh_programs):
    with FAULTS[fault]():
        result, log = run_small(21)
    assert not result["correct"], log
