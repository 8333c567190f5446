"""Scheduler, closed loop: time to first token at the client, median
(ms), of the requests sent inside the window: the wait for a slot (16
clients queue for 64 slots) plus prefill, time in which a client's
slot yields nothing."""
import statistics


def read(ctx):
    t = ctx["client"]["ttft_ms"]
    return statistics.median(t) if t else None
