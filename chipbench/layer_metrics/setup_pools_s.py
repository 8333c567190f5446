"""Set-up: seconds of the engine's constructor beyond the parameters
(EngineMetrics.boot_ms less boot_weights_ms): the KV pool, the state
slots and a draft model's (`engine.boot.pools`), and the rest of the
constructor. None for an engine that does not time its boot."""


def read(ctx):
    now, window = ctx["engine_now"], ctx["engine"]
    if "boot_ms" not in now:
        return None
    whole, weights = (now[k] - window.get(k, 0)
                      for k in ("boot_ms", "boot_weights_ms"))
    return (whole - weights) / 1e3
