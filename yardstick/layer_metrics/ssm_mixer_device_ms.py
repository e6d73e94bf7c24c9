"""ssm_mixer_device_ms (ms): device time per train step of everything under
`layer_<i>/mixer` of the state-space layers, forward and backward: the norm
before it, the in-projection, the convolution, the scan, the gated norm, the
out-projection and the residual's add, on the busiest chip over the profiled
interval (yardstick/ssm_scope_reduce.py)."""

from yardstick import ssm_scope_reduce


def read(run):
    ms = ssm_scope_reduce.per_step_ms(run)
    return None if ms is None else sum(ms[s] for s in ssm_scope_reduce.MIXER)
