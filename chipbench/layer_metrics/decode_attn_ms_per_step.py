"""Model step: device self time of one fused decode step spent in attention
(q/k/v projections, the page-walk kernel, the cache write, the output
projection: scope `attn`), inside `jit_multi_fn`, over dispatches x `k`,
the fused steps each `engine.launch` says it sent
(chipbench/hostspans.py), ms."""
from chipbench import hostspans


def read(ctx):
    return hostspans.step_ms(ctx, 'attn')
