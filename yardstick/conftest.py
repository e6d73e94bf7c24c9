"""One accepted test of the yardstick's suite asserts an accident of its day.

`tests/test_grouped_matmul_share.py::test_the_flagship_reports_no_such_metric`
(PR 29) ends on `manifest["per_layer"][-1] == <its own entry>`: true while
that entry was the newest. The benchmark's contract has every later PR
append its entries at the END of `per_layer` and edit no file the benchmark
has, so the first PR that adds a per-layer metric (PR 30) falsifies that
line and may not repair it. Until a `benchmark` PR drops the line, the test
is expected to fail on it, strictly: once it passes again this marker fails
the suite and has to go. What the test is there for (the entry itself, and
that the flagship is not in its `workloads`) is asserted again, by name, in
`tests/test_lm_kinds_train_step.py::test_the_accepted_metrics_stand`."""

import pytest

LAST_ENTRY_TEST = ("test_grouped_matmul_share.py::"
                   "test_the_flagship_reports_no_such_metric")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(LAST_ENTRY_TEST):
            item.add_marker(pytest.mark.xfail(
                strict=True, raises=AssertionError,
                reason="asserts per_layer[-1]; later PRs append after it"))
