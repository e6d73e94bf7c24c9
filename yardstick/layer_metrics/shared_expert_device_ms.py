"""shared_expert_device_ms (ms): device time per train step of the ops
under `mlp/shared`, the gated FFN every token runs beside the routed
experts, forward and backward, summed over the sparse layers, on the busiest
chip over the profiled interval (yardstick/kinds_scope_reduce.py)."""

from yardstick import kinds_scope_reduce


def read(run):
    ms = kinds_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["shared"]
