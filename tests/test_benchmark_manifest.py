"""The manifest's guard (one case an entry of `BENCHMARK.json`'s `per_layer`)
runs in tier-1 too: the cases are the benchmark's own."""

from yardstick.tests.test_benchmark_manifest import *  # noqa: F401,F403
