"""The yardstick: tpu_mpi's benchmark (BENCHMARK.json names its cells). Only
a `benchmark` PR may change a file here; every other PR adds files."""
