"""diff_extra_device_ms (ms): device time per train step of the ops under
`layer_<i>/attn/diff` alone, over all attention layers: lambda, the
subtraction of the second softmax's output from the first's, the RMSNorm
over each pair's 128 values and the scale, forward and backward: what
differential attention adds around the attention kernel's calls, on the
busiest chip over the profiled interval
(yardstick/sambay_scope_reduce.py)."""

from yardstick import sambay_scope_reduce


def read(run):
    ms = sambay_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["diff"]
