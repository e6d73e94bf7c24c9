"""kda_scan_device_ms (ms): device time per train step of the ops under
`layer_<i>/mixer/scan` of the KDA layers: the scan of
`parallel/delta.py:delta_scan` with a decay a key channel (the decay sums,
each chunk's decayed products by halves, its triangular system and inverse,
the chunk products, the state's chain over the chunks, all of it computed
again for the backward pass, and the backward chain), over all KDA layers,
on the busiest chip over the profiled interval; each of its loops by its
outermost `while` instruction's own event, what is nested in it left out
(yardstick/kda_scope_reduce.py)."""

from yardstick import kda_scope_reduce


def read(run):
    ms = kda_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["scan"]
