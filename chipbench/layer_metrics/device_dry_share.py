"""Engine loop: THE UNTRACED IDLE SHARE (%): host time during which the
device had nothing queued while the engine had work, as the engine's
dry clock measures it with no profiler running (`dry_before_ms` of each
dispatch: dynamo_tpu/telemetry/flight.py `DryClock`), summed over the
dispatches of the flight records BEFORE the traced slice, over the
seconds between the first and the last of those launches. The clock
asks at loop-phase boundaries, so it reads low by at most
`dry_slack_ms` (the `timeline` note carries it) and sees neither pauses
inside a program nor the launch latency. None for a program without
the clock."""
from chipbench import timeline


def read(ctx):
    return timeline.dry_share(timeline.of_part(ctx, "before"))
