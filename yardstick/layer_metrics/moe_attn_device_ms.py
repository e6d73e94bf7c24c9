"""moe_attn_device_ms (ms): device time per train step of the ops under a
layer's `attn` scope in a model with experts (norm, QKV, QK-norm, RoPE,
scores, softmax, the output projection and the residual add; forward, the
forward pass recomputed where the configuration says `remat_attn`, and
backward), summed over layers, on the busiest chip over the profiled
interval. `attn_device_ms` cannot be read here: it compiles the flagship's
step (yardstick/moe_scope_reduce.py)."""

from yardstick import moe_scope_reduce


def read(run):
    ms = moe_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["attn"]
