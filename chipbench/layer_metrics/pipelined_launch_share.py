"""Engine loop: how often a decode-carrying dispatch was already on the
device queue when its batch was scheduled (%): window delta of
EngineMetrics.overlap_hits (dispatches launched ahead of their batch
that turned out to BE the batch) over decode + mixed dispatches. 100
would be a loop that never launches with the queue empty; what is
missing are the dispatches behind a batch the host could not know
ahead (an arrival, a sampled stop, a rollback) and the separate decode
half of a mixed step that runs beside a late prefill. None where no
such dispatch ran, or the engine exports no such counter."""


def read(ctx):
    e = ctx["engine"]
    n = e.get("decode_dispatches", 0) + e.get("mixed_dispatches", 0)
    if not n or "overlap_hits" not in e:
        return None
    return 100.0 * e["overlap_hits"] / n
