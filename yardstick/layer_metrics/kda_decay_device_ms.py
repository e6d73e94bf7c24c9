"""kda_decay_device_ms (ms): device time per train step of the ops under
`layer_<i>/mixer/decay` of the KDA layers: what a decay a key channel costs
BEFORE the scan, the low-rank map's second product (rank -> heads x key
width), the bias, softplus and x -exp(a_log), a [tokens x heads x key width]
float32 array made forward, made again in the backward pass (it is never
kept) and differentiated there; over all KDA layers, on the busiest chip
over the profiled interval (yardstick/kda_scope_reduce.py)."""

from yardstick import kda_scope_reduce


def read(run):
    ms = kda_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["decay"]
