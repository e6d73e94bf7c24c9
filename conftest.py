"""Two accepted tests of the benchmark's own suite (`yardstick/tests`, outside
tier-1) assert an accident of their day: that THEIR entries are the last of
`BENCHMARK.json`'s `per_layer`. The benchmark's contract has every later PR
append its entries at the end of that list and edit no file the benchmark
has, so the next PR that adds a per-layer metric falsifies the line and may
not repair it. `yardstick/conftest.py` (PR 30) expects the first to fail,
`test_grouped_matmul_share.py::test_the_flagship_reports_no_such_metric`;
PR 30's own `test_lm_kinds_train_step.py::test_the_accepted_metrics_stand`
has the same line (`names[-17:] == mine`) and PR 32's append falsified it.
It is expected to fail here, strictly, until a `benchmark` PR drops the line;
a hook in `yardstick/` would be an edit to a file the benchmark has. What the
test is there for is asserted again, by name and by position from the end,
in `yardstick/tests/test_lm_latent_train_step.py::
test_the_accepted_metrics_stand`."""

import pytest

# (PR 33 appends three entries more, two of them to the openPangu cell:
# PR 32's own `names[-n:] == MINE`, and its line that the cell reports
# exactly its fifteen, are falsified in their turn; both are asserted again,
# by position from the end and with the three added, in
# `yardstick/tests/test_row_sum_product_share.py`.)
# (PR 34 appends six entries more, four of them to every cell: PR 33's own
# `per_layer[-3:] == MINE`, and its line that the openPangu cell reports
# exactly its twenty and K-EXAONE's list ends on its two, are falsified in
# their turn; both are asserted again, BY NAME and by no position, in
# `yardstick/tests/test_build_metrics.py`, whose own assertions no later
# append can falsify.)
# (PR 38 appends `scan_kernel_share` to the granite cell: PR 37's line that
# the cell reports exactly its sixteen, `sorted(names) == sorted(...)`, is
# falsified in its turn, though its docstring says no append can; every name
# it lists is asserted again, as a subset, in
# `yardstick/tests/test_scan_kernel_share.py`.)
# (PR 42 appends `sel_scan_kernel_share` to the phi cell: PR 41's line that
# the cell reports exactly its twenty-four, `sorted(names) == sorted(...)`,
# is falsified in its turn; every name it lists is asserted again, as a
# subset, in `yardstick/tests/test_sel_scan_kernel_share.py`.)
# (PR 45 appends a seventh train cell to `blocked_head_share`'s and
# `train_tokens_per_s`'s lists: PR 43's two lines that those lists ARE its
# six cells, `spec == {..., "workloads": TRAIN_CELLS}` and `(NAME in names)
# == (w["name"] in TRAIN_CELLS)`, are falsified in their turn; the entry,
# the six cells in it and which cells report it are asserted again, by name
# and as a subset, in `yardstick/tests/test_lm_gdn_train_step.py`. `per_layer`
# is held to 128 entries and had 123, so that cell reads the accepted
# readers under the accepted entries, its name appended to their lists:
# PR 34's line that the six build entries' lists ARE its cells,
# `by_name[name] == {..., "workloads": cells}`, is falsified too, and is
# asserted again in the same file, the cells as a subset.)
# (PR 48 appends an eleventh cell to `delta_chunked_share`'s list, whose reader
# finds the Kimi cell's scans as it finds Qwen3-Next's: PR 45's line that its
# five new entries' lists ARE its one cell, `by_name[name]["workloads"] ==
# [CELL]`, is falsified in its turn; the five entries, that cell first in
# each and this one in `delta_chunked_share` alone, are asserted again in
# `yardstick/tests/test_lm_kda_train_step.py`. `per_layer` was full at 128:
# that file's own assertions are by name and as subsets.)
LAST_ENTRIES_TESTS = (
    "yardstick/tests/test_lm_kinds_train_step.py::"
    "test_the_accepted_metrics_stand",
    "yardstick/tests/test_lm_latent_train_step.py::"
    "test_the_accepted_metrics_stand",
    "yardstick/tests/test_lm_latent_train_step.py::"
    "test_the_cell_reports_what_the_issue_names",
    "yardstick/tests/test_row_sum_product_share.py::"
    "test_the_entries_follow_what_the_benchmark_had",
    "yardstick/tests/test_row_sum_product_share.py::"
    "test_the_held_cells_report_them",
    "yardstick/tests/test_lm_ssm_train_step.py::"
    "test_the_cell_reports_what_the_issue_names",
    "yardstick/tests/test_lm_sambay_train_step.py::"
    "test_the_cell_reports_what_the_issue_names",
    "yardstick/tests/test_blocked_head_share.py::"
    "test_the_entry_by_name",
    "yardstick/tests/test_blocked_head_share.py::"
    "test_the_six_train_cells_report_it_and_no_other_cell_does",
    "yardstick/tests/test_build_metrics.py::"
    "test_the_six_entries_by_name_and_content",
    "yardstick/tests/test_lm_gdn_train_step.py::"
    "test_the_cell_reports_what_the_issue_names")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(LAST_ENTRIES_TESTS):
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="asserts its entries are per_layer's last; later PRs "
                       "append after them"))
