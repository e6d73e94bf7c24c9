"""expert_load_max_over_mean (ratio): the busiest expert's token-slots over
the mean (tokens x experts per token / experts), in the worst layer, of the
batches routed just before and just after the window. Source: the program's
own count (`transformer_expert_counts`), which the generator reads outside
the timed samples. 1.0 is perfect balance. Nothing is padded or dropped,
and still the grouped multiplications' time follows it on one chip: one
layer at the cell's shapes, forward and backward, took 34.5 ms with every
expert at the mean, 42.4 ms with the busiest at 7.5 x (no expert idle) and
35.8 ms with all rows in 8 experts (my chip run, PR 25; PERF.md section 6).
An expert-parallel layout's time will follow it more."""

EXACT_COUNT = True      # a count: a CPU rehearsal may report it


def read(run):
    counts = run.facts.get("expert_counts")
    if not counts:
        return None
    return max(max(layer) * len(layer) / sum(layer)
               for when in counts.values() for layer in when)
