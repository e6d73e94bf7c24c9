"""embed_device_ms (ms): device time per train step under `embed`: the gather
of the tokens' rows from the embedding and, in the backward pass, the sum of
their gradients back into the embedding's rows (4096 or 8192 rows into
[19200, d]: XLA's scatter-add until PR 33, since then the product
`parallel.ep.rows_at` selects), with the transposing copies of the embedding
that carry its name (yardstick/kinds_scope_reduce.py)."""

from yardstick import kinds_scope_reduce


def read(run):
    ms = kinds_scope_reduce.per_step_ms(run)
    return None if ms is None else ms["embed"]
