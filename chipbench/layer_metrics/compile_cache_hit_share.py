"""Set-up: of the compiles before the window whose result jax's
persistent cache holds (answered from it, or written to it), the share
it answered (EngineMetrics.compile_cache_hits over
compile_cache_requests at the opening, %): 100 is a warm run, 0 a cold
one, anything between a cache that lost entries. A compile too quick
for jax to keep (the `feed` helpers) is in neither count. 0.0 where
there was none. None for an engine that does not split its first
calls."""


def read(ctx):
    now, window = ctx["engine_now"], ctx["engine"]
    if "compile_cache_requests" not in now:
        return None
    asked, hit = (now[k] - window.get(k, 0) for k in
                  ("compile_cache_requests", "compile_cache_hits"))
    return 100.0 * hit / asked if asked else 0.0
