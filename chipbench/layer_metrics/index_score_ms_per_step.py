"""Model step: device self time of one fused decode step spent in the
learned indexer's projections and scores (scope `attn/index` of every
layer: `W_qI`, `W_kI` and its LayerNorm, `W_w`, their rotary embedding,
the gather of each row's index keys through its page table and the
scores of 16 heads over every cached token), inside `jit_multi_fn`, over
dispatches x `k` (chipbench/indexscopes.py), ms. Plain XLA; bound: memory
(the index keys). It is part of what `decode_attn_ms_per_step` reads.
None where the trace names no such scope (every other configuration, the
parent commit)."""
from chipbench import indexscopes


def read(ctx):
    s = indexscopes.step_seconds(ctx, "attn/index")
    return None if s is None else 1e3 * s
