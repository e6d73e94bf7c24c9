"""build_compile_s (s): seconds of set-up inside JAX's backend compile
requests (`/jax/core/compile/backend_compile_duration`), as the program's
own listener summed them: `build.compile.s` of the pvar snapshot at the
window's begin (`yardstick/build_reduce.py`). A request that the persistent
cache answers is one too, and the seconds of its read lie in here: in a warm
run this is what reading the executables took, in a cold one XLA's and
Mosaic's compile (`build_cache_misses` says which run it was). Summed over threads: four
rank threads that compile or read at once add up, past the wall time where
they overlap, so in an OSU cell this is work, not a share of `setup_s`."""

from yardstick import build_reduce


def read(run):
    return build_reduce.phase_seconds(run, "compile")
