"""build_lower_s (s): seconds of set-up that JAX spent lowering jaxprs to
MLIR modules (`/jax/core/compile/jaxpr_to_mlir_module_duration`; a Mosaic
kernel's lowering, about 0.10 s each, lies in here), as the program's own
listener summed them: `build.lower.s` of the pvar snapshot at the window's
begin (`yardstick/build_reduce.py`). The persistent cache saves none of it:
the module is the cache's key. Summed over threads: rank threads that lower
at once add up, past the wall time where they overlap."""

from yardstick import build_reduce


def read(run):
    return build_reduce.phase_seconds(run, "lower")
