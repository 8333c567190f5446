"""Set-up: step programs the engine loaded before the window opened
(EngineMetrics.compiles at the opening)."""


def read(ctx):
    return float(ctx["engine_now"]["compiles"]
                 - ctx["engine"].get("compiles", 0))
