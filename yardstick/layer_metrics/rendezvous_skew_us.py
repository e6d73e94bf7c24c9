"""rendezvous_skew_us (us): a waiter's time from its own deposit to the last
arriver's: waiting for a late peer, the part of the rendezvous no host-path
optimisation inside the program can close. The `rdv_skew` span, total
seconds over the sampled ops of the profiled interval, all ranks, per `op`
span (the last arriver has none, so three ranks of four carry it)."""

from yardstick import span_reduce

prepare = span_reduce.prepare


def read(run):
    return span_reduce.part_us(run, "rdv_skew")
