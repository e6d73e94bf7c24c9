"""head_loss_device_ms (ms): device time per train step of the ops whose
scope is `head_loss` (the final norm, the tied head's matmul over the
vocabulary and the cross-entropy, forward and backward), on the busiest
chip over the profiled interval (yardstick/scope_reduce.py)."""

from yardstick import scope_reduce


def read(run):
    ms = scope_reduce.per_step_ms(run)
    return None if ms is None else ms["head_loss"]
