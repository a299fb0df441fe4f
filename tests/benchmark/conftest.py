"""Two assertions of earlier PRs' tests say that ``BENCHMARK.json``'s lists
END with those PRs' own entries (the last cell is the gated-convolution
cell; the last six per-layer metrics are the block's parts). The
builders' contract has every later entry put at the end of its list ("one
put first or in the middle reads as a change to what was there", and a PR
that changes an entry is refused before a run), so the first PR that adds
a cell or a reader makes the two false, and a PR that is no ``benchmark``
PR may edit no file the benchmark has. Putting this PR's entries before
theirs would keep the two true and break the contract; leaving them to
fail would make the repo's tests worse than they stood. So the two are
marked as expected to fail, strictly, for that one assertion each, and
``test_benchmark_sdar.py`` runs both functions whole on the lists as they
stood before this PR (``test_the_two_marked_tests_hold_whole_before_this_pr``)
and holds the entries' order with what was appended
(``test_the_earlier_entries_stand_where_they_stood``): no assertion of
theirs goes unexecuted. The ``benchmark`` PR that makes the
two say 'in this order, before whatever came later' takes this file
away (PERF.md section 7)."""

import pytest

APPENDED_TO = {
    "test_benchmark_lfm2.py::"
    "test_the_cell_reports_the_two_readings_and_no_other_cell_does":
        "asserts that the benchmark's last cell is lfm2-8b-a1b-l8-e8-s8192; "
        "two cells were appended after it",
    "test_benchmark_block_parts.py::"
    "test_the_six_are_appended_and_none_is_reported_everywhere":
        "asserts that the benchmark's last six per-layer metrics are the "
        "block's parts; two readers were appended after them",
}


def pytest_collection_modifyitems(items):
    for item in items:
        for name, reason in APPENDED_TO.items():
            if item.nodeid.endswith(name):
                item.add_marker(pytest.mark.xfail(reason=reason,
                                                  strict=True))
