"""ssm_scan_device_ms (ms): device time per train step of the ops under
`layer_<i>/mixer/scan`: dt's softplus, the decays, the chunked products of
`parallel/ssm.py:scan` and the skip term, forward, what the backward pass
computes again, and backward, over all state-space layers, on the busiest
chip over the profiled interval (yardstick/ssm_scope_reduce.py)."""

from yardstick import ssm_scope_reduce


def read(run):
    ms = ssm_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["scan"]
