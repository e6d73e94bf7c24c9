"""fused_attn_share (%): of the attention calls built into the programs
traced before the window (the step's layers, and where the generator runs
the program's forward pass for its checks, those too), how many the program
lowered as its fused causal kernel and not as the plain einsum / softmax /
einsum path that writes [b, h, t, t] scores to HBM. The process-wide pair
`attn_lowerings` of `perfvars.snapshot()` at the window's begin, after
warm-up has compiled everything the window runs: `fused` over `fused` +
`plain`. 100 where `parallel.ring.local_attention` selects the kernel (a
TPU, a shape inside the kernel's contract), 0 where it leaves the shape to
the plain path. A program without the counter has nothing to read."""


def read(run):
    built = run.counters.get("begin", {}).get("attn_lowerings")
    if not built:
        return None
    fused, plain = int(built.get("fused", 0)), int(built.get("plain", 0))
    if not fused + plain:
        return None
    return 100.0 * fused / (fused + plain)
