"""moe_head_loss_device_ms (ms): device time per train step of the ops
under `head_loss` in a model with experts (the final norm, the untied
head's matmul over the vocabulary and the cross-entropy, forward and
backward), on the busiest chip over the profiled interval
(yardstick/moe_scope_reduce.py)."""

from yardstick import moe_scope_reduce


def read(run):
    ms = moe_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["head_loss"]
