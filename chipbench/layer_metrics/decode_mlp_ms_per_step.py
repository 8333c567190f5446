"""Model step: device self time of one fused decode step spent in the gated
MLP with its norm and residual (scope `mlp`), inside `jit_multi_fn`,
over dispatches x `k`, the fused steps each `engine.launch` says it sent
(chipbench/hostspans.py), ms."""
from chipbench import hostspans


def read(ctx):
    return hostspans.step_ms(ctx, 'mlp')
