"""moe_device_ms (ms): device time per train step of the ops under a
layer's `mlp` scope in a model with experts (the norm before the router,
`router`, `dispatch`, `experts`, `combine`, the residual add; forward and
backward), summed over layers, on the busiest chip over the profiled
interval. The reader prints every scope and the unscoped rest
(yardstick/moe_scope_reduce.py)."""

from yardstick import moe_scope_reduce


def read(run):
    ms = moe_scope_reduce.per_step_ms(run)
    return None if ms is None else sum(ms[s] for s in moe_scope_reduce.MOE)
