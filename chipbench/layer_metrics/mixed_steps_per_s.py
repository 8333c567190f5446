"""Model step: fused mixed dispatches a second, COUNTED, untraced:
timeline entries of kind `mixed` in the flight records before the traced
slice over the seconds between the first and the last launch there.
With `mixed_step_ms_p50` it says what share of a second the one-token
step that admits a prompt takes. None for a program without the
timeline."""
from chipbench import timeline


def read(ctx):
    return timeline.mixed_steps_per_s(timeline.of_part(ctx, "before"))
