"""sel_scan_kernel_share (%): of the selective (Mamba-1) scans counted in the
programs traced before the window (once for each trace of a mamba layer's
kind: the step's, and the generator's forward programs'), how many the
program computes with its Pallas kernel pair
(`tpu_mpi/xla/sel_scan_kernels.py` behind `parallel/ssm.py:selective_scan`)
and not with the plain `lax.scan`s of `_selective_chunks`. The process-wide
pair `sel_scan_kernel_lowerings` of `perfvars.snapshot()` at the window's
begin, after warm-up has compiled everything the window runs: `kernel` over
`kernel` + `plain`. 100 where the backend and the shapes select the kernels,
0 where they leave the scan to XLA (the CPU, channels that are no multiple
of 512, a state that is not 16 wide). A program without the counter (the
parent of the PR that added it) has nothing to read."""


def read(run):
    built = run.counters.get("begin", {}).get("sel_scan_kernel_lowerings")
    if not built:
        return None
    kernel, plain = int(built.get("kernel", 0)), int(built.get("plain", 0))
    if not kernel + plain:
        return None
    return 100.0 * kernel / (kernel + plain)
