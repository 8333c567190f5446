"""Set-up: seconds between the engine constructor's exit
(EngineMetrics.boot_end_perf_s, on the clock the window's `t0` is
stamped with) and the window's opening that were NOT first calls: the
server's start and the ramp's traffic served by programs already
loaded. None for an engine that does not time its boot."""


def read(ctx):
    now, window = ctx["engine_now"], ctx["engine"]
    if "boot_end_perf_s" not in now:
        return None
    boot_end = now["boot_end_perf_s"] - window.get("boot_end_perf_s", 0)
    first_calls_ms = now["compile_ms"] - window.get("compile_ms", 0)
    return ctx["t0"] - boot_end - first_calls_ms / 1e3
