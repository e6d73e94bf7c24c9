"""attn_device_ms (ms): device time per train step of the ops whose scope
is a layer's `attn` (norm, QKV, RoPE, scores, softmax, the output
projection and the residual add, forward and backward), summed over
layers, on the busiest chip over the profiled interval. The reader prints
all five scopes and the unscoped rest (yardstick/scope_reduce.py)."""

from yardstick import scope_reduce


def read(run):
    ms = scope_reduce.per_step_ms(run)
    return None if ms is None else ms["attn"]
