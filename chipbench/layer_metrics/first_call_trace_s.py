"""Set-up: seconds jax spent TRACING step programs before the window
opened (EngineMetrics.compile_trace_ms at the opening: event
`/jax/core/compile/jaxpr_trace_duration`, the outermost of each first
call). Host Python, cache or no cache. None for an engine that does not
split its first calls."""


def read(ctx):
    now = ctx["engine_now"]
    if "compile_trace_ms" not in now:
        return None
    return (now["compile_trace_ms"]
            - ctx["engine"].get("compile_trace_ms", 0)) / 1e3
