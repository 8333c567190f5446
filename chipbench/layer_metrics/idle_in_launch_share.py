"""Engine loop: share of the traced slice in which the chip sat idle while
a step program is being launched (a first call compiles here): device
idle gaps under the engine thread's `engine.launch` span
(chipbench/hostspans.py), %. With its seven siblings it sums to
`device_idle_share`."""
from chipbench import hostspans


def read(ctx):
    return hostspans.idle_share(ctx, "launch")
