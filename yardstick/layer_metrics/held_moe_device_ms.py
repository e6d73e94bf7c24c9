"""held_moe_device_ms (ms): device time per train step of the routed half
of the sparse layers on a chip that holds a share of the experts: `router`
(128 scores a token, top 8) + `dispatch` (the sort of the token-slots, held
experts first, and the gather of their rows) + `experts` (the held experts'
grouped products) + `combine` (the rows added into their tokens' places),
forward and backward, summed over the sparse layers, on the busiest chip
over the profiled interval (yardstick/kinds_scope_reduce.py)."""

from yardstick import kinds_scope_reduce


def read(run):
    ms = kinds_scope_reduce.per_step_ms(run)
    return None if ms is None else sum(
        ms[s] for s in kinds_scope_reduce.HELD_MOE)
