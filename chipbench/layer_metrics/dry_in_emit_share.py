"""Engine loop: share of the untraced seconds in which the device had
nothing queued while the runner posted a step's outputs to the request
queues (%): the per-step deltas of EngineMetrics.dry_emit_ms (the dry
clock's time under `engine.emit`) in the flight records before the
traced slice, over their seconds. The untraced twin of
`idle_in_emit_share`. None for a program without the clock."""
from chipbench import timeline


def read(ctx):
    return timeline.delta_share(
        timeline.of_part(ctx, "before"), "dry_emit_ms")
