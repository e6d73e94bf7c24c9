"""host_overhead_us (us): per-op wall time minus per-op device busy time,
over the profiled interval: what one collective call costs beyond the work
the busiest chip did for it. Wall time and op count are the harness's own
(the interval between two block boundaries), busy time is the union of the
busiest chip's op intervals in the trace."""


def read(run):
    ops = run.traced_ops()
    if not ops:
        return None
    return (run.trace.window_s - run.trace.busiest.busy_s) / ops * 1e6
