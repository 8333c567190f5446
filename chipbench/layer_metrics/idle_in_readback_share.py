"""Engine loop: share of the traced slice in which the chip sat idle while
the host is still inside its blocking read of sampled ids (the tail
after the device's last operation): device idle gaps under the engine
thread's `engine.readback` span (chipbench/hostspans.py), %. With its
seven siblings it sums to `device_idle_share`."""
from chipbench import hostspans


def read(ctx):
    return hostspans.idle_share(ctx, "readback")
