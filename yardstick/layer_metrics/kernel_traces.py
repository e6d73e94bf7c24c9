"""kernel_traces (count): Pallas kernels built into the programs that
set-up traced, one for each `pallas_call` a wrapper of
`tpu_mpi/xla/pallas_kernels.py` built under a trace (the step's, and the
generator's forward programs'): the sum of `build.kernels` of the pvar
snapshot at the window's begin (`yardstick/build_reduce.py`), printed by
name. Each costs set-up a trace of the kernel's body and, where the program
keeps it, a Mosaic lowering (PERF.md section 5, "Set-up": about 0.15 s and
0.10 s). Nothing where no kernel was built."""

from yardstick import build_reduce


def read(run):
    fam = build_reduce.family(run)
    if fam is None or not fam["kernels"]:
        return None
    run.row("kernels built under a trace: " + "  ".join(
        f"{name} x{n}" for name, n in fam["kernels"].items()))
    return int(sum(fam["kernels"].values()))
