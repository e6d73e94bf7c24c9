"""delta_chunked_share (%): of the delta-rule scans counted in the programs
traced before the window (once for each trace of a delta-rule layer's kind:
the step's, and the generator's forward programs'), how many run over whole
chunks as they stand and not over a sequence filled up to the next multiple
of the chunk (`tpu_mpi/parallel/delta.py:delta_scan`). The process-wide pair
`delta_lowerings` of `perfvars.snapshot()` at the window's begin, after
warm-up has compiled everything the window runs: `chunked` over `chunked` +
`padded`. 100 where the sequence is a multiple of the chunk. A program
without the counter (the parent of the PR that added it) has nothing to
read."""

EXACT_COUNT = True      # a count: a CPU rehearsal may report it


def read(run):
    built = run.counters.get("begin", {}).get("delta_lowerings")
    if not built:
        return None
    chunked, padded = int(built.get("chunked", 0)), int(built.get("padded", 0))
    if not chunked + padded:
        return None
    return 100.0 * chunked / (chunked + padded)
