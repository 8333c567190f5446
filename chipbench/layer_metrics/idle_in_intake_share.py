"""Engine loop: share of the traced slice in which the chip sat idle while
the runner drains its inbox (admissions, aborts, deadlines): device idle
gaps under the engine thread's `engine.intake` span
(chipbench/hostspans.py), %. With its seven siblings it sums to
`device_idle_share`."""
from chipbench import hostspans


def read(ctx):
    return hostspans.idle_share(ctx, "intake")
