"""moe_dispatch_device_ms (ms): device time per train step under `router`,
`dispatch` and `combine` (the router's matrix and softmax, top-k, the sort
of the token-slots by expert, the gathers into and out of expert order, the
weighted sum), forward and backward, summed over layers: what the sparsity
costs beyond its matrix multiplications (yardstick/moe_scope_reduce.py)."""

from yardstick import moe_scope_reduce


def read(run):
    ms = moe_scope_reduce.per_step_ms(run)
    if ms is None:
        return None
    return ms["router"] + ms["dispatch"] + ms["combine"]
