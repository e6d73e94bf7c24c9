"""sel_scan_device_ms (ms): device time per train step of the ops under
`layer_<i>/mixer/scan` of the mamba layers: the selective scan of
`parallel/ssm.py:selective_scan` (a decay a token, channel and state index,
the recurrence over the sequence a chunk at a time, its chunks computed again
for the backward pass, and the backward recurrence), over all mamba layers,
on the busiest chip over the profiled interval; each of its loops by its
outermost `while` instruction's own event, what is nested in it left out
(yardstick/sambay_scope_reduce.py:nested_in_loops)."""

from yardstick import sambay_scope_reduce


def read(run):
    ms = sambay_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["scan"]
