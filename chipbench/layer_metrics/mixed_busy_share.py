"""Model step: the mixed dispatches' share of the device's busy time,
untraced (%): the sum of their `dev_ms` over the sum of every dispatch's
`dev_ms`, in the flight records before the traced slice (dispatches
whose time on the device is not known are in neither sum). None for a
program without the timeline."""
from chipbench import timeline


def read(ctx):
    return timeline.mixed_busy_share(timeline.of_part(ctx, "before"))
