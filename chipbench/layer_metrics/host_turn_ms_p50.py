"""Engine loop: the host turn a dispatch on the device has to cover,
untraced, median (ms): per dispatch of the flight records before the
traced slice, from the return of the readback before it (`t_ready`) to
the return of its own program call (`t_launch`): postprocess, emit,
intake, schedule, stage and launch together (`host_ms_per_dispatch` is
the postprocess alone). A turn longer than the dispatch then running
lets the chip run dry. None for a program without the timeline."""
from chipbench import timeline


def read(ctx):
    return timeline.host_turn_ms(timeline.of_part(ctx, "before"), 50)
