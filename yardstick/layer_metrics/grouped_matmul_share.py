"""grouped_matmul_share (%): of the experts' grouped multiplications counted
in the programs traced before the window (three for each trace of a layer
with experts: the step's, and where the generator runs the program's forward
pass for its checks and its counter, that one's too), how many the program
lowered as its grouped Pallas kernel and not as `lax.ragged_dot`, XLA's own.
The process-wide pair `gmm_lowerings` of `perfvars.snapshot()` at the
window's begin, after warm-up has compiled everything the window runs:
`kernel` over `kernel` + `ragged_dot`. 100 where
`parallel.ep.grouped_products` selects the kernel (a TPU, a shape inside the
kernel's contract), 0 where it leaves the shape to `ragged_dot`. A program
without the counter has nothing to read."""


def read(run):
    built = run.counters.get("begin", {}).get("gmm_lowerings")
    if not built:
        return None
    kernel, plain = int(built.get("kernel", 0)), int(built.get("ragged_dot", 0))
    if not kernel + plain:
        return None
    return 100.0 * kernel / (kernel + plain)
