"""between_ops_us (us): what the caller did between two host-path ops of its
thread, `op`.t0 - `t_prev` (`t_prev`: when the thread's previous op ended,
on the `op` span since PR 51): in the cell's per-op rung the wait for the
result (`block_until_ready`) and the loop, what an MPI profile calls
application time. Mean over the sampled ops of that rung, all ranks, host
clock alone. With the `op` bracket it tiles the op's period, from the end
of its thread's previous op to its own; the reader prints the sum beside the
profiled interval's wall time an op less the chip's busy time, over ALL ops
(`host_overhead_us`' own arithmetic): over 100% by what a sampled op takes
longer than one that is not. Reads nothing on a program without `t_prev`
(yardstick/ready_reduce.py)."""

from yardstick import ready_reduce, span_reduce

prepare = span_reduce.prepare


def read(run):
    return ready_reduce.between_ops_us(run)
