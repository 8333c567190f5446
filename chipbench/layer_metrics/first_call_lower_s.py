"""Set-up: seconds jax spent LOWERING step programs to MLIR before the
window opened (EngineMetrics.compile_lower_ms at the opening: event
`/jax/core/compile/jaxpr_to_mlir_module_duration`; a Pallas kernel's
Mosaic module is lowered inside it). Host work, cache or no cache. None
for an engine that does not split its first calls."""


def read(ctx):
    now = ctx["engine_now"]
    if "compile_lower_ms" not in now:
        return None
    return (now["compile_lower_ms"]
            - ctx["engine"].get("compile_lower_ms", 0)) / 1e3
