"""Model step: device self time of a step under scope `mlp/moe/route` in
the cell `cmdaplus-longctx` (the float32 router over 128 experts at the
highest precision, the sigmoid, the 8 highest, the share's assignments
ordered by counting and the weighted sum back, 4 layers), a MIXED step:
over the WHOLE `jit_mixed_fn` dispatches of the trace
(chipbench/dots3scopes.py), ms. None where the trace names no such scope."""
from chipbench import cmdaplusscopes, dots3scopes


def read(ctx):
    if cmdaplusscopes.layers(ctx) is None:
        return None
    s = dots3scopes.step_seconds(ctx, "mlp/moe/route")
    return None if s is None else 1e3 * s
