"""One test of this directory pins the number of cells in BENCHMARK.json.

``test_bench_deepseek.py::test_the_document_and_the_configuration_keep_the_contract``
asserts that the document has exactly 5 cells and that DeepSeek-V3's is the
last: true when PR 34 wrote it, and false for every PR that adds a cell after
it.  A PR that is not a ``benchmark`` PR may add files here and may not edit
one that is there, so the test is marked as an expected failure here, by name,
and every other line of it is asserted again, without the count, in
``test_bench_cohere.py::test_the_dsv3_cell_keeps_its_contract_beside_a_newer_cell``.
The mark is strict: once a ``benchmark`` PR drops the two pins the test passes
again, this mark then fails the run, and that PR drops this file with them.
"""

import pytest

PINNED = "test_bench_deepseek.py::test_the_document_and_the_configuration_keep_the_contract"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(PINNED):
            item.add_marker(pytest.mark.xfail(
                reason="pins 5 cells and its own as the last; PR 38 added a sixth (tests/benchmark/conftest.py)", strict=True,
            ))
