"""Model step: device self time of one fused decode step spent choosing
pages (scope `attn/select` of the four sparse layers: the gather of each
row's compressed keys, their scores under the KV head's 16 query heads,
the softmax, the pool onto blocks, two sorts of 288 blocks and the list
for the walk), inside `jit_multi_fn`, over dispatches x `k`
(chipbench/sparsescopes.py), ms. Plain XLA; bound: latency and small
gathers. It is part of what `decode_attn_ms_per_step` reads. None where
the trace names no such scope (every other configuration, the parent
commit)."""
from chipbench import sparsescopes


def read(ctx):
    s = sparsescopes.select_step_seconds(ctx)
    return None if s is None else 1e3 * s
