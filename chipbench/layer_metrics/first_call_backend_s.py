"""Set-up: seconds of XLA's compile, or of the persistent cache's read
in its place, for the step programs loaded before the window opened
(EngineMetrics.compile_backend_ms at the opening: event
`/jax/core/compile/backend_compile_duration`). None for an engine that
does not split its first calls."""


def read(ctx):
    now = ctx["engine_now"]
    if "compile_backend_ms" not in now:
        return None
    return (now["compile_backend_ms"]
            - ctx["engine"].get("compile_backend_ms", 0)) / 1e3
