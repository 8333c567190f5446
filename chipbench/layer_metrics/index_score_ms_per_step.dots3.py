"""Model step: device time of the decode rows' index scores a step in
dots3-note-prev's 3 full layers: the kernel `paged_index_scores` (a
128-wide key a cached token read out of the pool in place, 64 heads;
ops/index_scores.py `paged=False`), its own events under scope `attn/index`
in the WHOLE dispatches of the trace, fused decode steps and the decode
rows' part of mixed steps alike (chipbench/dots3scopes.py), ms a step.
Bound: memory (the index keys). Part of what `decode_attn_ms_per_step`
reads. None where no such kernel ran under a window layer's program (every
other configuration, the parent commit)."""
from chipbench import dots3scopes


def read(ctx):
    s = dots3scopes.decode_kernel_step_seconds(
        ctx, "paged_index_scores", "attn/index")
    return None if s is None else 1e3 * s
