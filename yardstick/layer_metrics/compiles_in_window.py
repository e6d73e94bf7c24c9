"""compiles_in_window (count): backend compile requests between window
start and end, counted by the harness from `jax.monitoring`
(`/jax/core/compile/backend_compile_duration`; a hit in the persistent
cache is a request too). Must be 0, or the window held set-up work."""

EXACT_COUNT = True      # repeats exactly, so a CPU rehearsal may report it


def read(run):
    return run.compiles_in_window
