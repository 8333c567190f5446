"""Model step: how near its HBM floor the sliding layers' decode attention
runs (%): the roofline share of the kernel `paged_decode_attention` under
scope `attn/window`. The ring rows a window holds (513 rows x 2,176 B a
decode row and sliding layer: `window_read_bytes` of
`chipbench/costs_dots3.py` on the decode rows of the traced slice's fused
and mixed dispatches) over the kernel's own events a step, WHOLE
dispatches of both kinds (chipbench/dots3scopes.py), over the chip's peak
HBM bandwidth. The bytes are the least a window must read (the walk reads 9
whole pages of 64 rows with the rope key in its lane tile, 1.19 times
that), so the share cannot pass 100. Bound: memory. None where no such
kernel ran or the cost module has no answer."""
from chipbench import costs, dots3scopes


def read(ctx):
    peaks = ctx.get("peaks")
    window_read_bytes = costs.asked(ctx, "window_read_bytes")
    step_s = dots3scopes.decode_kernel_step_seconds(
        ctx, "paged_decode_attention", "attn/window")
    at = dots3scopes.decode_steps(ctx)
    if window_read_bytes is None or not peaks or not step_s or not at:
        return None
    nbytes = window_read_bytes(ctx["hf"], ctx["weights"], at["live"],
                               at["rows"], ctx["kernels"])
    return 100.0 * nbytes / step_s / peaks["hbm_bytes_per_s"]
