"""device_idle_share (%): 1 - (union of the device's op intervals) /
(profiled interval), on the busiest chip of the cell (run.py prints every
chip's on an earlier line; `device.busy_s` in the result line is the
average over chips). Also read under `device_idle_share.<tag>` where the
cell's end-to-end metric is another (harness.Cell.readers)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.busiest.idle_share
