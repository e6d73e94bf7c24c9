"""copyout_us (us): host time to put the round's result into the caller's
receive buffer, the enqueueing of its move to the rank's own chip included
(`copyout`, today's pvar phase `copy`: a dispatch time). Total seconds over
the sampled ops of the profiled interval, all ranks, per `op` span."""

from yardstick import span_reduce

prepare = span_reduce.prepare


def read(run):
    return span_reduce.part_us(run, "copyout")
