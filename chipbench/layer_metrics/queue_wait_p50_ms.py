"""Scheduler: wait for a slot, arrival to admission, median over the
requests the window's steps admitted (`admit_wait_ms` of the flight
records: every request, traced or not), ms. `ttft_p50_ms.closed` less
this is prefill and the way back to the client."""
import statistics


def read(ctx):
    waits = [w for r in ctx["flight"] for w in r.get("admit_wait_ms", ())]
    return statistics.median(waits) if waits else None
