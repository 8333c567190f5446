"""Model step: device self time of a step under scope `mlp/moe/route` in
the cell `dots3-longctx` (the float32 router over 256 experts at the
highest precision, the sigmoid, the 8 of highest corrected score, the
assignments ordered by expert by counting and the weighted un-sort, 8
expert layers), a MIXED step: over the WHOLE `jit_mixed_fn` dispatches of
the trace (chipbench/dots3scopes.py: 85-89 % of the cell's time; a fused
decode step's route is a tenth of it and a slice holds 0 to 5 of those
dispatches), ms. None where the trace names no such scope."""
from chipbench import dots3scopes


def read(ctx):
    s = dots3scopes.step_seconds(ctx, "mlp/moe/route")
    return None if s is None else 1e3 * s
