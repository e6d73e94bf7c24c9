"""idle_outside_us (us): of the busiest chip's idle gaps that end where a
sampled round's fold starts on the device, the seconds before the round's
last arriver had entered its `op` (`outside the program`: the caller's own
time, and the previous round's completion on its way back), per sampled
round whose gaps were cut. `span_reduce.attribute_gaps`' cut with the
device plane moved to the middle of its window, as `idle_launch_us`
(yardstick/ready_reduce.py)."""

from yardstick import ready_reduce, span_reduce

prepare = span_reduce.prepare


def read(run):
    return ready_reduce.idle_cut_us(run, "outside the program")
