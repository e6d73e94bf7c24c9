"""gdn_scan_device_ms (ms): device time per train step of the ops under
`layer_<i>/mixer/scan` of the delta-rule layers: the scan of
`parallel/delta.py:delta_scan` (the decay sums, each chunk's triangular
system and its inverse, the chunk products, the state's chain over the
chunks, all of it computed again for the backward pass, and the backward
chain), over all delta-rule layers, on the busiest chip over the profiled
interval; each of its loops by its outermost `while` instruction's own
event, what is nested in it left out (yardstick/gdn_scope_reduce.py)."""

from yardstick import gdn_scope_reduce


def read(run):
    ms = gdn_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["scan"]
