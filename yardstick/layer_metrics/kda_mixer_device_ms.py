"""kda_mixer_device_ms (ms): device time per train step of everything under
`layer_<i>/mixer` of the KDA (delta rule, a decay a key channel) layers,
forward and backward: the norm before it, the in-projections, the
convolution, the L2 norms, the decay, the scan, the norm and gate, the
out-projection and the residual's add, on the busiest chip over the profiled
interval (yardstick/kda_scope_reduce.py)."""

from yardstick import kda_scope_reduce


def read(run):
    ms = kda_scope_reduce.per_step_ms(run)
    return None if ms is None else sum(ms[s] for s in kda_scope_reduce.KDA_ALL)
