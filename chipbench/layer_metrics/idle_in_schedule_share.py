"""Scheduler: share of the traced slice in which the chip sat idle while
the scheduler picks the next batch (`schedule()` and the doomed drain):
device idle gaps under the engine thread's `engine.schedule` span
(chipbench/hostspans.py), %. With its seven siblings it sums to
`device_idle_share`."""
from chipbench import hostspans


def read(ctx):
    return hostspans.idle_share(ctx, "schedule")
