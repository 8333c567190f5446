"""Engine loop: `device_dry_share` INSIDE the traced slice (%): the
same sum over the flight records whose `ts` lies between the slice's
wall-clock ends. Its yardstick is `device_idle_share` of the same run
(the device trace of the same seconds): their agreement is what
validates the dry clock; their distance from `device_dry_share` is what
the profiler does to the loop it watches. None for a program without
the clock."""
from chipbench import timeline


def read(ctx):
    return timeline.dry_share(timeline.of_part(ctx, "inside"))
