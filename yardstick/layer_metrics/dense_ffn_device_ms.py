"""dense_ffn_device_ms (ms): device time per train step of the ops under
`mlp/dense`, the leading layer's gated FFN of width 18432, forward, the
forward pass again where the configuration recomputes it, and backward, on
the busiest chip over the profiled interval
(yardstick/kinds_scope_reduce.py)."""

from yardstick import kinds_scope_reduce


def read(run):
    ms = kinds_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["dense"]
