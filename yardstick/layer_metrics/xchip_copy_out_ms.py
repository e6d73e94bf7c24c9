"""xchip_copy_out_ms (ms): from the moment a round's fold had its output
ready on the folding chip to the moment the last rank had the result on its
own chip: the star's second half, completion to completion on the watcher's
clock (tpu_mpi/perfvars.py `watch`: one thread that waits for the arrays in
the order they were handed over, so a completion is never stamped before
the one handed over ahead of it). Median over the profiled interval's
stamped rounds. Rank 0, whose chip folds, copies nothing out. The copy-in
has no such pair of completions: its span begins at a dispatch the host
made rounds ahead, and is printed beside this, not reported
(yardstick/span_reduce.py `watched_rounds`)."""

from yardstick import stats, span_reduce

prepare = span_reduce.prepare


def read(run):
    rounds = span_reduce.watched_rounds(run)
    if rounds is None:
        return None
    return stats.median([(r["home"] - r["fold"]["t1"]) * 1e3 for r in rounds])
