"""Engine loop: speculated next dispatches thrown away per step that
admitted a request (window delta of EngineMetrics.overlap_rollbacks
over mixed + prefill dispatches). Near 0 when the loop foresees the
batch change and speculates nothing; 1 would be a dispatch of device
work wasted at every admission."""


def read(ctx):
    e = ctx["engine"]
    n = e.get("mixed_dispatches", 0) + e.get("prefill_dispatches", 0)
    if not n or "overlap_rollbacks" not in e:
        return None
    return e["overlap_rollbacks"] / n
