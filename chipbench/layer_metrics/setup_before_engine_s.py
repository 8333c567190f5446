"""Set-up: seconds from the process's start, as the OS has it, to the
engine constructor's entry (EngineMetrics.boot_before_ms): the
interpreter, the imports of jax and of the package, the backend's
start, the tokenizer, argument parsing. None for an engine that does
not time its boot."""


def read(ctx):
    now = ctx["engine_now"]
    if "boot_before_ms" not in now:
        return None
    return (now["boot_before_ms"]
            - ctx["engine"].get("boot_before_ms", 0)) / 1e3
