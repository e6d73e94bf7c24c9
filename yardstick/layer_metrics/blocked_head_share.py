"""blocked_head_share (%): of the vocabulary heads and their losses counted in
the programs traced before the window (once for each trace of a train step),
how many the program computes over blocks of tokens with both gradients made
in the forward pass (`tpu_mpi/models/transformer.py:head_loss`: three
products a block, no [tokens, vocab] float32 array held) and not as the
whole float32 logits differentiated by JAX (`_xent`). The process-wide pair
`head_loss_lowerings` of `perfvars.snapshot()` at the window's begin, after
warm-up has compiled everything the window runs: `blocked` over `blocked` +
`whole`. 100 where the step is `transformer_train_step`, whatever number of
blocks its rule chose (`head_loss_blocks` beside it says which; one block is
the same code), 0 for a step that keeps the whole logits. A program without
the counter (the parent of the PR that added it) has nothing to read."""


def read(run):
    built = run.counters.get("begin", {}).get("head_loss_lowerings")
    if not built:
        return None
    blocked, whole = int(built.get("blocked", 0)), int(built.get("whole", 0))
    if not blocked + whole:
        return None
    return 100.0 * blocked / (blocked + whole)
