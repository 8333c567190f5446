"""Engine loop: share of the untraced seconds in which the device had
nothing queued while the host built and transferred a dispatch's arrays
(%): the per-step deltas of EngineMetrics.dry_stage_ms (the dry clock's
time under `engine.stage`) in the flight records before the traced
slice, over their seconds. The untraced twin of `idle_in_stage_share`.
None for a program without the clock."""
from chipbench import timeline


def read(ctx):
    return timeline.delta_share(
        timeline.of_part(ctx, "before"), "dry_stage_ms")
