"""Model step: device self time of a step under scope `attn/select` in
the cell `dots3-longctx` (the exact top 2,048 of each row's index scores
in 3 full layers: 32 counting passes over the scores' bits, no sort:
ops/token_select.py, shared with `keye-longctx`), a MIXED step: over the
WHOLE `jit_mixed_fn` dispatches of the trace (the decode rows' selection
and a prompt chunk's under one scope; 85-89 % of the cell's time, and a
slice holds 0 to 5 fused dispatches: chipbench/dots3scopes.py), ms. None
where the trace names no such scope."""
from chipbench import dots3scopes


def read(ctx):
    s = dots3scopes.step_seconds(ctx, "attn/select")
    return None if s is None else 1e3 * s
