"""held_slot_share (%): of the token-slots the sparse layers' routers sent
(tokens x experts per token a layer, over all the router's experts), the
share that landed on the experts this chip holds; the batches routed just
before and just after the window, all sparse layers together. Source: the
program's own counter (`transformer_held_counts`), read outside the timed
samples. 6.25 when 8 of 128 experts are held and the router is balanced."""

EXACT_COUNT = True      # a count: a CPU rehearsal may report it


def read(run):
    held = run.facts.get("held")
    if not held:
        return None
    slots = sum(sum(when["slots"]) for when in held.values())
    return 100.0 * sum(sum(when["held"]) for when in held.values()) / slots \
        if slots else None
