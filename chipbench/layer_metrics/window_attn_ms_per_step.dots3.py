"""Model step: device time of the decode rows' window attention a step in
dots3-note-prev's 6 sliding layers: the kernel `paged_decode_attention`
walking the 9 of a slot's 17 ring pages a window can reach under a bit a
ring row, 64 heads (models/dots3.py `window_attend`), its own events under
scope `attn/window` in the WHOLE dispatches of the trace, fused decode
steps and the decode rows' part of mixed steps alike
(chipbench/dots3scopes.py), ms a step. Bound: memory (the ring rows). Part
of what `decode_attn_ms_per_step` reads. None where no such kernel ran
under that scope (every other configuration, the parent commit)."""
from chipbench import dots3scopes


def read(ctx):
    s = dots3scopes.decode_kernel_step_seconds(
        ctx, "paged_decode_attention", "attn/window")
    return None if s is None else 1e3 * s
