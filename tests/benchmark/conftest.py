"""One test of this directory asserts that PR 23's per-layer entries are
the LAST of ``BENCHMARK.json`` and that nothing else of the file differs
from that PR's parent (test_trace_scopes.py::
test_new_entries_are_appended_and_found).  It held while no later PR added
an entry.  PR 30 appends a configuration, a cell and seven entries, as a
cell-adding PR must (new entries go at the end), and may not edit a file
the benchmark already has: so the test is marked as expected to fail here,
visibly, and a ``benchmark`` PR should anchor it to its own entries' place
instead of the list's end.  What it guarded is asserted for the present
list by test_lm_cell.py::test_the_cell_came_as_new_files_and_appended_entries.
"""

import pytest

SUPERSEDED = ("test_trace_scopes.py::test_new_entries_are_appended_and_found",)


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(SUPERSEDED):
            item.add_marker(pytest.mark.xfail(
                reason="asserts that PR 23's entries end BENCHMARK.json; "
                       "PR 30 appended a cell (see this conftest)",
                strict=False))
