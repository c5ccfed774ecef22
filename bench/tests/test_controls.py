"""The comparison that decides ``correct`` must refuse what is wrong.

Each cell runs as ``bench/run.py`` runs it, past the look for a chip, on
the CPU at a size a test run holds (Pallas kernels in interpret mode):

- as it is, where it must come out correct;
- with its control in the program's place, the plain reference at
  ``Precision.HIGH`` (three bfloat16 passes) where the configuration
  states float32 at HIGHEST, on three seeds;
- with each fault of ``bench/faults.py`` planted in the timed path
  underneath: an answer altered where it is produced, half of the ground
  set's rows left out of the mean, and a call that hands back the last
  call's answer unchanged. (The cells run on one chip, so there is no
  exchange between chips to leave out.)
"""
import io

import jax
import pytest

from bench import faults, run

SMALL = {
    "paper_v_a.multiset": dict(overrides=dict(
        n=1024, l=128, k=10, d=100, backend="pallas_interpret")),
    # k=48 is past the flat kernel's VMEM limit: the loop variant runs
    "paper_v_a_k500.multiset": dict(overrides=dict(
        n=512, l=16, k=48, d=100, backend="pallas_interpret")),
}
CELLS = sorted(SMALL)


def run_small(cell, seed, control=False):
    log = io.StringIO()
    result = run.run_cell(cell, seed, 1.0, False, need_chip=False,
                          control=control, log=log, **SMALL[cell])
    return result, log.getvalue()


@pytest.fixture
def fresh_programs():
    """Programs traced before a fault was planted would hide it."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    result, log = run_small(cell, 2**31 + 11)
    assert result["correct"], log
    assert result["failed"] == 0 and result["attempted"] > 0


@pytest.mark.parametrize("seed", [5, 6, 2**32 + 7])
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_refused(cell, seed):
    result, log = run_small(cell, seed, control=True)
    assert not result["correct"], log


def cell_faults(cell):
    _, _, traffic = run.cell_parts(cell)
    return faults.FAULTS[traffic["generator"]]


FAULTS = [(cell, name) for cell in CELLS for name in cell_faults(cell)]


@pytest.mark.parametrize("cell,fault", FAULTS)
def test_fault_is_refused(cell, fault, fresh_programs):
    with cell_faults(cell)[fault]():
        result, log = run_small(cell, 21)
    assert not result["correct"], log
