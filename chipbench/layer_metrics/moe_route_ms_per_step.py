"""Model step: device self time of one fused decode step spent routing
(the gate, top-k, the sort of the assignments by expert and the weighted
un-sort: scope `mlp/moe/route`), inside `jit_multi_fn`, over dispatches
x `k` (chipbench/subscopes.py), ms. Bound: latency (sorts and gathers
of a few hundred rows, once an expert layer). None where the trace names
no such scope."""
from chipbench import subscopes


def read(ctx):
    s = subscopes.step_seconds(ctx, "mlp/moe/route")
    return None if s is None else 1e3 * s
