"""attn_window_device_ms (ms): device time per train step of the ops under
`layer_<i>/attn` in the layers whose attention has a window: norm, the q, k,
v projections, QK-norm, RoPE, the fused kernel forward and backward, the
output projection and the residual add; summed over those layers, on the
busiest chip over the profiled interval (yardstick/kinds_scope_reduce.py)."""

from yardstick import kinds_scope_reduce


def read(run):
    ms = kinds_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["attn_window"]
