"""test_benchmark_rehearsal.py's cells 2, 5, ... of every three: that file
says why the chain of child processes is three files'."""
import pytest

from test_benchmark_rehearsal import FILES, _cells, _rehearse


@pytest.mark.parametrize("manifest,workload,chips", _cells()[2::FILES])
def test_rehearsal(manifest, workload, chips, tmp_path):
    _rehearse(manifest, workload, chips, tmp_path)
