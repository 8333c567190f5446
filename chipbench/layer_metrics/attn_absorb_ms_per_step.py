"""Model step: device self time of one fused decode step spent absorbing
the key up-projection into the query (`q_lat = q_nope . W_UK`: scope
`attn/absorb`), inside `jit_multi_fn`, over dispatches x `k`
(chipbench/subscopes.py), ms. None where the trace names no such scope
(a decoder without a latent cache)."""
from chipbench import subscopes


def read(ctx):
    s = subscopes.step_seconds(ctx, "attn/absorb")
    return None if s is None else 1e3 * s
