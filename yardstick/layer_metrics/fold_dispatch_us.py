"""fold_dispatch_us (us): host time the round's last arriver takes to launch
the fold, its operand copies included (`fold_dispatch`, today's pvar phase
`fold`: a DISPATCH time, the device works on after it). Total seconds over
the sampled ops of the profiled interval, all ranks, per `op` span: one
rank in four carries it, so the per-round dispatch is four times this where
there are four ranks. Also read under `fold_dispatch_us.<tag>` where the
cell's end-to-end metric is another (harness.Cell.readers)."""

from yardstick import span_reduce

prepare = span_reduce.prepare


def read(run):
    return span_reduce.part_us(run, "fold_dispatch")
