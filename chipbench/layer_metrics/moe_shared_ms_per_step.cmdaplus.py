"""Model step: device self time of a step under scope `mlp/moe/shared` in
the cell `cmdaplus-longctx` (the four shared experts of 4,096 as one gated
MLP of 16,384 whose output is scaled by 1/4, whole on every chip, 4
layers: 1.6 GB of weights a step), a MIXED step: over the WHOLE
`jit_mixed_fn` dispatches of the trace (chipbench/dots3scopes.py), ms. None
where the trace names no such scope."""
from chipbench import cmdaplusscopes, dots3scopes


def read(ctx):
    if cmdaplusscopes.layers(ctx) is None:
        return None
    s = dots3scopes.step_seconds(ctx, "mlp/moe/shared")
    return None if s is None else 1e3 * s
