"""Model step: device self time of one fused decode step spent in the
output head and the sampler (scopes `lm_head` and `sample`), inside
`jit_multi_fn`, over dispatches x `k`, the fused steps each
`engine.launch` says it sent (chipbench/hostspans.py), ms."""
from chipbench import hostspans


def read(ctx):
    return hostspans.step_ms(ctx, 'lm_head', 'sample')
