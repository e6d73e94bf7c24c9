"""gmu_device_ms (ms): device time per train step of the ops under
`layer_<i>/mixer/gmu` of the gated memory units: the in-projection, the
memory x silu gate and the out-projection, forward and backward (the
memory's gradient among them), over those layers, on the busiest chip over
the profiled interval (yardstick/sambay_scope_reduce.py)."""

from yardstick import sambay_scope_reduce


def read(run):
    ms = sambay_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["gmu"]
