"""Device: share of the traced slice in which the chip sat idle under no
phase of the engine loop: the thread waited for work (`engine.wait`),
was inside `engine.step` but between phases, or wrote no span at all
(chipbench/hostspans.py), %. Small, or the loop has a phase no span
names."""
from chipbench import hostspans


def read(ctx):
    return hostspans.idle_share(ctx, "unattributed")
