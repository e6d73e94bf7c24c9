"""Until PR 50 twelve tests of this suite asserted an accident of their day
(that THEIR entries were the last of `BENCHMARK.json`'s `per_layer`, or the
whole of a cell's list), every later append falsified the line, and no PR
but a `benchmark` one may edit a file the benchmark has: so `/conftest.py`
marked eleven of them strict expected failures and this file the twelfth.
PR 50 dropped those lines (every assertion about the manifest is now by name
and as a subset) and this file's marker with them. `/conftest.py` is outside
the benchmark's `paths`, a `benchmark` PR may not touch it, and its strict
markers would now fail the eleven tests for PASSING: the hook below takes
them off again. It does nothing once a PR that may edit `/conftest.py` has
emptied `LAST_ENTRIES_TESTS` there; the next `benchmark` PR then cuts this
file to its docstring."""

import pytest

STALE_REASON = "asserts its entries are per_layer's last"


@pytest.hookimpl(trylast=True)         # after /conftest.py's hook has marked
def pytest_collection_modifyitems(items):
    for item in items:
        item.own_markers[:] = [
            m for m in item.own_markers if not (
                m.name == "xfail"
                and STALE_REASON in m.kwargs.get("reason", ""))]
