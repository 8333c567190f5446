"""Engine loop: share of the traced slice in which the chip sat idle while
the host builds a dispatch's arrays and transfers them: device idle gaps
under the engine thread's `engine.stage` span (chipbench/hostspans.py),
%. With its seven siblings it sums to `device_idle_share`."""
from chipbench import hostspans


def read(ctx):
    return hostspans.idle_share(ctx, "stage")
