"""Engine loop: `host_turn_ms_p50`'s 95th percentile (ms): the turns
that outlast a dispatch are in this tail. None for a program without
the timeline."""
from chipbench import timeline


def read(ctx):
    return timeline.host_turn_ms(timeline.of_part(ctx, "before"), 95)
