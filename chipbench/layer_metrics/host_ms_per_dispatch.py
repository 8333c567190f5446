"""Engine loop: host postprocessing (stop scan, page registration) per
decode-carrying dispatch — window delta of EngineMetrics
time_decode_host_ms over decode + mixed dispatches (ms)."""


def read(ctx):
    e = ctx["engine"]
    n = e.get("decode_dispatches", 0) + e.get("mixed_dispatches", 0)
    return e.get("time_decode_host_ms", 0.0) / n if n else None
