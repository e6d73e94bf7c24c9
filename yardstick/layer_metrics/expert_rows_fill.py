"""expert_rows_fill (%): the rows the held experts computed over the rows
the sparse layers gathered for them (the buffer's size, times the buffers
that ran): the static-shape answer in one number. The batches routed just
before and just after the window, all sparse layers together. Source: the
program's own counter (`transformer_held_counts`: what
`parallel.ep.moe_dropless_held` says it did). 50 when the router is balanced
and the buffer is twice the expected rows; a layer whose further buffers ran
(more slots arrived than one buffer holds; nothing is dropped) gathers eight
buffers and reads low, and the reader's line says how often that was."""

EXACT_COUNT = True      # a count: a CPU rehearsal may report it


def read(run):
    held = run.facts.get("held")
    if not held:
        return None
    gathered = sum(sum(when["gathered"]) for when in held.values())
    fallbacks = sum(sum(when["fallbacks"]) for when in held.values())
    run.row(f"held experts' row buffers: further buffers ran in {fallbacks} "
            f"of {sum(len(w['fallbacks']) for w in held.values())} layer "
            "passes counted")
    return 100.0 * sum(sum(when["computed"]) for when in held.values()) \
        / gathered if gathered else None
