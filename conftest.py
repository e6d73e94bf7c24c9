"""Keeps the checkout's root on `sys.path` for the test suites (`pyproject.toml`
names no `pythonpath`; pytest puts the directory of a root `conftest.py`
there). It marks nothing: the eleven strict expected failures it carried for
`yardstick/tests` until PR 51 all pass since PR 50 dropped the lines a later
append falsified."""
