"""fold_ready_us (us): from the moment the last arriver's launch of the fold
returned (`fold_dispatch`.t1) to the moment the program's watcher saw the
fold's output ready (`fold.done`.t1, tpu_mpi/perfvars.py `watch`: since
PR 51 stamped on one chip as on four): the device's end of the fold as the
HOST's clock has it, no device plane needed. Median over the sampled rounds
of the rung synced per op that have both stamps. The reader prints beside it
the fold's device time from the trace's `XLA Modules` events and what is
left: the launch path and the completion's way to a waiting thread. Reads
nothing on a program without `fold.done` on this lane
(yardstick/ready_reduce.py)."""

from yardstick import ready_reduce, span_reduce

prepare = span_reduce.prepare


def read(run):
    return ready_reduce.fold_ready_us(run)
