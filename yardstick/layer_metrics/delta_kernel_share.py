"""delta_kernel_share (%): of the delta-rule scans counted in the programs
traced before the window (once for each trace of a delta-rule layer's kind:
the step's, and the generator's forward programs'), how many the Pallas
kernel pair computes (`tpu_mpi/xla/delta_kernels.py`) and not the plain path
(`tpu_mpi/parallel/delta.py:_chunked`). The process-wide pair
`delta_kernel_lowerings` of `perfvars.snapshot()` at the window's begin,
after warm-up has compiled everything the window runs: `kernel` over
`kernel` + `plain`. 0 where the kernel's contract refuses the operands (a
decay a key channel, as many key heads as value heads): what a kernel for
them moves. A program without the counter has nothing to read."""

EXACT_COUNT = True      # a count: a CPU rehearsal may report it


def read(run):
    built = run.counters.get("begin", {}).get("delta_kernel_lowerings")
    if not built:
        return None
    kernel, plain = int(built.get("kernel", 0)), int(built.get("plain", 0))
    if not kernel + plain:
        return None
    return 100.0 * kernel / (kernel + plain)
