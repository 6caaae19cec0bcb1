"""test_chip_smoke.py's phases 1, 4, ... of every three: that file says why
the phases are three files'."""
import pytest

from test_chip_smoke import FILES, PHASES, tiny_rehearsal_passes


@pytest.mark.parametrize("letter", PHASES[1::FILES])
def test_tiny_rehearsal_passes_every_phase(letter):
    tiny_rehearsal_passes(letter)
