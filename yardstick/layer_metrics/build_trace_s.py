"""build_trace_s (s): seconds of set-up that JAX spent tracing Python into
jaxprs (`/jax/core/compile/jaxpr_trace_duration`), as the program's own
listener summed them: `build.trace.s` of the pvar snapshot at the window's
begin (`yardstick/build_reduce.py`). Outermost traces only: a jitted
function traced under another is in its caller's seconds. A kernel's body
trace (about 0.15 s each, `kernel_traces`) lies in here. Summed over
threads: rank threads that trace at once add up, past the wall time where
they overlap (a train cell builds on one thread)."""

from yardstick import build_reduce


def read(run):
    return build_reduce.phase_seconds(run, "trace")
