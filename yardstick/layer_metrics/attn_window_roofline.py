"""attn_window_roofline (%): the fused attention kernel in the window
layers against the chip's bf16 peak. Least time = the kernel's products AS
EXECUTED (lm_kinds_flops.attn_kernel_flops: only the pairs of blocks on the
window's band, each multiplied whole, 2 products forward and 5 backward, at
the block size the program chose) x the kernel's calls a step that the
trace counts, over `bf16_flops` of peaks.json; divided by the device time of
`causal_attention_fwd|bwd` in those layers. Bound by compute. It says how
well the kernel multiplies what it visits, not how much of that a window of
128 needs: a 512-wide pair on the band holds 4 to 16 masked scores for each
one seen. A reading over 100 means the count is wrong. The reader prints the
kernel's calls and time by kind and direction."""

from yardstick import kinds_scope_reduce


def read(run):
    return kinds_scope_reduce.attn_roofline(run, "window")
