"""held_dispatch_device_ms (ms): device time per train step under `dispatch`
and `combine` on a chip that holds a share of the experts (the sort of the
token-slots, held experts first, the gather of their rows into the buffer,
and the weighted rows added into their tokens' places), forward and
backward, summed over the sparse layers: what the static-shape answer costs
beside the held experts' products, the part of `held_moe_device_ms` that an
exchange over an `ep` axis would replace (yardstick/kinds_scope_reduce.py)."""

from yardstick import kinds_scope_reduce


def read(run):
    ms = kinds_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["dispatch"] + ms["combine"]
