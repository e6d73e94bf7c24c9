"""front_door_us (us): what a collective call costs before it reaches the
channel: on the armed lane the front door of `Allreduce` (one dict probe,
identity compares, `contrib()`), on the legacy lane argument parsing, the
plan and the auto-arm gate. The `front_door` span (op entry -> the entry of
`CollectiveChannel.run`), total seconds over the sampled ops of the
profiled interval, all ranks, per `op` span (yardstick/span_reduce.py)."""

from yardstick import span_reduce

prepare = span_reduce.prepare


def read(run):
    return span_reduce.part_us(run, "front_door")
