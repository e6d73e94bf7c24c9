"""attn_full_device_ms (ms): device time per train step of the ops under
`layer_<i>/attn` in the layers whose attention is full (window 0 in the
configuration's `model` block): norm, the q, k, v projections, QK-norm,
the fused kernel forward and backward (no RoPE there), the output
projection and the residual add; summed over those layers, on the busiest
chip over the profiled interval (yardstick/kinds_scope_reduce.py)."""

from yardstick import kinds_scope_reduce


def read(run):
    ms = kinds_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["attn_full"]
