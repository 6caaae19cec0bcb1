"""test_kernel_entries_trace_once.py's families 1, 3, ... of every two: that
file says why the families are two files'."""
import pytest

from test_kernel_entries_trace_once import FAMILIES, FILES, \
    a_kernel_entry_traces_once_a_shape


@pytest.mark.parametrize("family", sorted(FAMILIES)[1::FILES])
def test_a_kernel_entry_traces_once_a_shape(family):
    a_kernel_entry_traces_once_a_shape(family)
