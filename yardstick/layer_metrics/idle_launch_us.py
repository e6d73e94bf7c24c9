"""idle_launch_us (us): of the busiest chip's idle gaps that end where a
sampled round's fold starts on the device, the seconds after the last
arriver's dispatch had returned (`launch`: PJRT's launch path, seen from
the host as nothing), per sampled round whose gaps were cut. It is
`span_reduce.attribute_gaps`' cut, which `idle_attributed_share` only
prints, made with the device plane moved to where the program's stamps
allow it: the cut places a DEVICE event among HOST spans and takes a round's
fold to be the first to start after its dispatch began, and the profiler
puts the plane up to 1.5 ms from there. The reading is the cut at the middle
of `device_clock_window_us`' window; the row gives it at the window's two
ends too, and the stamps tell `launch` from `outside the program` no nearer
than that. On a program without `fold.done` there is no window and the cut
is the accepted reader's (yardstick/ready_reduce.py)."""

from yardstick import ready_reduce, span_reduce

prepare = span_reduce.prepare


def read(run):
    return ready_reduce.idle_cut_us(run, "launch")
