"""attn_full_roofline (%): the fused attention kernel in the full-attention
layers against the chip's bf16 peak. Least time = the kernel's products AS
EXECUTED (lm_kinds_flops.attn_kernel_flops: the pairs of blocks on and below
the diagonal, each multiplied whole, 2 products forward and 5 backward) x
the kernel's calls a step that the trace counts, over `bf16_flops` of
peaks.json; divided by the device time of `causal_attention_fwd|bwd` in
those layers. Bound by compute. A reading over 100 means the count is
wrong."""

from yardstick import kinds_scope_reduce


def read(run):
    return kinds_scope_reduce.attn_roofline(run, "full")
