"""gdn_mixer_device_ms (ms): device time per train step of everything under
`layer_<i>/mixer` of the delta-rule (linear attention) layers, forward and
backward: the norm before it, the in-projections, the convolution, the L2
norms and the decays, the delta-rule scan, the norm and gate, the
out-projection and the residual's add, on the busiest chip over the profiled
interval (yardstick/gdn_scope_reduce.py)."""

from yardstick import gdn_scope_reduce


def read(run):
    ms = gdn_scope_reduce.per_step_ms(run)
    return None if ms is None else sum(ms[s] for s in gdn_scope_reduce.GDN_ALL)
