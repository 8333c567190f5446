"""Engine loop: share of the traced slice in which the chip sat idle while
the host scans sampled ids for stops and registers pages: device idle
gaps under the engine thread's `engine.postprocess` span
(chipbench/hostspans.py), %. With its seven siblings it sums to
`device_idle_share`."""
from chipbench import hostspans


def read(ctx):
    return hostspans.idle_share(ctx, "postprocess")
