"""step_device_ms (ms): device busy time per train step, over the profiled
interval: the union of the busiest chip's op intervals in the trace, over
the steps the harness ran between the interval's two block boundaries."""


def read(run):
    steps = run.traced_ops()
    if not steps:
        return None
    return run.trace.busiest.busy_s / steps * 1e3
