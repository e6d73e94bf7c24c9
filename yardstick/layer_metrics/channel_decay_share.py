"""channel_decay_share (%): of the delta-rule scans counted in the programs
traced before the window, how many decay their state by a vector, one
number a key channel (`g` [batch, tokens, heads, key width]), and not by one
number a head (`tpu_mpi/parallel/delta.py:delta_scan`). The process-wide
pair `delta_decays` of `perfvars.snapshot()` at the window's begin:
`channel` over `channel` + `head`. 100 in a model whose every delta-rule
layer is KDA. A program without the counter (the parent of the PR that added
it) has nothing to read."""

EXACT_COUNT = True      # a count: a CPU rehearsal may report it


def read(run):
    built = run.counters.get("begin", {}).get("delta_decays")
    if not built:
        return None
    channel, head = int(built.get("channel", 0)), int(built.get("head", 0))
    if not channel + head:
        return None
    return 100.0 * channel / (channel + head)
