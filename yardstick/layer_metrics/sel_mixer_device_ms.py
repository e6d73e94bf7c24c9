"""sel_mixer_device_ms (ms): device time per train step of everything under
`layer_<i>/mixer` of the mamba (Mamba-1) layers, forward and backward: the
LayerNorm before it, the in-projection, the convolution, the projections of
dt, B and C, the selective scan, the gate, the out-projection and the
residual's add, on the busiest chip over the profiled interval
(yardstick/sambay_scope_reduce.py)."""

from yardstick import sambay_scope_reduce


def read(run):
    ms = sambay_scope_reduce.per_step_ms(run)
    return None if ms is None else sum(
        ms[s] for s in sambay_scope_reduce.MAMBA_ALL)
