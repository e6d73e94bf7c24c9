"""kinds_head_loss_device_ms (ms): device time per train step of the ops
under `head_loss` in a model of layer kinds (the final norm, the untied
head's matmul over the vocabulary rows that are here and the float32
cross-entropy, forward and backward), on the busiest chip over the profiled
interval (yardstick/kinds_scope_reduce.py)."""

from yardstick import kinds_scope_reduce


def read(run):
    ms = kinds_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["head_loss"]
