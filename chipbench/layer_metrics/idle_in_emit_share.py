"""Engine loop: share of the traced slice in which the chip sat idle while
the runner posts a step's outputs to the request queues: device idle
gaps under the engine thread's `engine.emit` span
(chipbench/hostspans.py), %. With its seven siblings it sums to
`device_idle_share`."""
from chipbench import hostspans


def read(ctx):
    return hostspans.idle_share(ctx, "emit")
