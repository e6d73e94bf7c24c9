"""diff_attn_device_ms (ms): device time per train step of everything under
`layer_<i>/attn` of the differential attention layers (window, full and
cross): LayerNorm, the projections with their biases, the cut into heads,
the fused kernel's calls forward and backward, the subtraction, the pair
norm, the output projection and the residual's add, on the busiest chip over
the profiled interval (yardstick/sambay_scope_reduce.py)."""

from yardstick import sambay_scope_reduce


def read(run):
    ms = sambay_scope_reduce.per_step_ms(run)
    return None if ms is None else sum(ms[s] for s in sambay_scope_reduce.ATTN)
