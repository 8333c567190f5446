"""Model step: device time of the decode rows' window attention a step in
Command A+'s 3 sliding layers: the kernel `paged_decode_attention` walking
the 65 of a slot's 72 ring pages a window of 4,096 can reach under a bit a
ring row, 128 query heads over 8 KV heads (models/cohere2_moe.py
`window_attend`), its own events under scope `attn/window` in the WHOLE
dispatches of the trace, fused decode steps and the decode rows' part of
mixed steps alike (chipbench/dots3scopes.py), ms a step. Bound: memory (the
ring rows). None where no such kernel ran under that scope."""
from chipbench import cmdaplusscopes, dots3scopes


def read(ctx):
    if cmdaplusscopes.layers(ctx) is None:
        return None
    s = dots3scopes.decode_kernel_step_seconds(
        ctx, "paged_decode_attention", "attn/window")
    return None if s is None else 1e3 * s
