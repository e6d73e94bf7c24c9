"""rendezvous_wake_us (us): a waiter's time from "results published" to the
moment its own thread runs again: the condition variable's notify and the
hand-over of the GIL between four rank threads. The `rdv_wake` span, total
seconds over the sampled ops of the profiled interval, all ranks, per `op`
span."""

from yardstick import span_reduce

prepare = span_reduce.prepare


def read(run):
    return span_reduce.part_us(run, "rdv_wake")
