"""Set-up: seconds the engine's constructor spent on the parameters
(EngineMetrics.boot_weights_ms, span `engine.boot.weights`): loaded or
drawn on the device, quantized, placed, and waited for. None for an
engine that does not time its boot."""


def read(ctx):
    now = ctx["engine_now"]
    if "boot_weights_ms" not in now:
        return None
    return (now["boot_weights_ms"]
            - ctx["engine"].get("boot_weights_ms", 0)) / 1e3
