"""Set-up: seconds of every first call of a step program before the
window opened, whole (EngineMetrics.compile_ms at the opening: what it
reads at the window's close less the window's own): jax's trace and
lowering, XLA's compile or the cache's read, the first run."""


def read(ctx):
    return (ctx["engine_now"]["compile_ms"]
            - ctx["engine"].get("compile_ms", 0)) / 1e3
