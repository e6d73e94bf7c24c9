"""device_clock_window_us (us): the room in which the device plane can lie on
the host's clock without contradicting a stamp of the program's. A CHECK ON
THE YARDSTICK, not a target: it measures how far the profiler's placement of
the plane can be trusted and how finely the stamps hold it, and no change to
the program's hot path moves it (only a new stamp would). A sampled
round's fold cannot have started on the device before its last arriver
began to dispatch it (`fold_dispatch`.t0), nor can the watcher have seen its
output ready (`fold.done`.t1) before the device was done: a fold event can
be that round's only under shifts of the plane between those two bounds.
The metric is the width of the window of shifts that satisfies every sampled
round (the most of them, where not every one). Where the plane sits as the
profiler put it, that is the smallest (device start - dispatch's begin) plus
the smallest (`fold.done` - device end); where it does not, the window lies
to one side of no shift, the row says by how much the plane has to move,
how many rounds contradict a stamp as it was placed, and, the rounds'
folds found under the window, what the stamps say of a fold's two halves:
dispatch -> device start and device end -> `fold.done`.

`span_reduce` holds the HOST lines to `time.monotonic()` to under a
microsecond; this holds the DEVICE plane from both sides, which every
reader that places a device event among host spans leans on
(`idle_attributed_share`'s cut; `idle_launch_us` and `idle_outside_us`
make theirs with the plane moved to this window's middle). A
round of the large rung is known from a small one's by the least time its
bytes take at the chip's peak: small rounds, a period apart, would let the
plane slip by whole periods. Reads nothing on a program without `fold.done`
on this lane (yardstick/ready_reduce.py)."""

from yardstick import ready_reduce, span_reduce

prepare = span_reduce.prepare


def read(run):
    return ready_reduce.device_clock_window_us(run)
