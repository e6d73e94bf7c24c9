"""ssm_conv_device_ms (ms): device time per train step of the ops under
`layer_<i>/mixer/conv`: the causal depthwise convolution (four taps and a
bias), silu and the cut into x, B and C, forward and backward, over all
state-space layers, on the busiest chip over the profiled interval
(yardstick/ssm_scope_reduce.py)."""

from yardstick import ssm_scope_reduce


def read(run):
    ms = ssm_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["conv"]
