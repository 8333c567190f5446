"""Engine loop: dispatches launched with the device EMPTY over all
dispatches, untraced (%): timeline entries of the flight records before
the traced slice that carry `dry_before_ms` (the dry clock found the
newest launch's output ready before this one was made:
dynamo_tpu/telemetry/flight.py `DryClock`) over all entries. What
`pipelined_launch_share` was meant to be the complement of: that one
says a launch came ahead of its BATCH, this one that it came behind the
DEVICE. Its check: the window delta `dry_launches` / `launches`. None for
a program without the clock."""
from chipbench import timeline


def read(ctx):
    return timeline.late_launch_share(timeline.of_part(ctx, "before"))
