"""Engine loop: programs compiled inside the measured window (window
delta of EngineMetrics.compiles). 0 expected: warm-up touched them."""


def read(ctx):
    return float(ctx["engine"].get("compiles", 0))
