"""Model step: device self time of one fused decode step spent in the
Mamba-2 mixers (in_proj, the conv and its window, the state update, the
gated norm, out_proj: scopes `attn/ssm/*`), inside `jit_multi_fn`, over
dispatches x `k` (chipbench/ssmscopes.py), ms. It is part of what
`decode_attn_ms_per_step` reads. None where the trace names no such
scope (every other configuration, the parent commit)."""
from chipbench import ssmscopes


def read(ctx):
    s = ssmscopes.step_seconds(ctx, *ssmscopes.SSM)
    return None if s is None else 1e3 * s
