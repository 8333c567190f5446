"""Model step: how near its HBM floor the sliding layers' decode attention
runs (%): the roofline share of the kernel `paged_decode_attention` under
scope `attn/window` in the cell `cmdaplus-longctx`. K and V of the ring rows
IN REACH (`min(context, 4,096)` x 4,096 B a decode row and sliding layer:
`window_read_bytes` of `chipbench/costs_command_a_plus.py` on the count the
step programs make on the device, `walk_pages_named` in the flight records
of the traced slice's fused and mixed dispatches) over the kernel's own
events a step, WHOLE dispatches of both kinds (chipbench/dots3scopes.py),
over the chip's peak HBM bandwidth. The bytes are the least a window must
read (the walk reads 65 whole pages, 4,160 rows), so the share cannot pass
100. Bound: memory. None where no such kernel ran, the program counts
nothing or the cost module has no answer."""
from chipbench import cmdaplusscopes, costs, dots3scopes


def read(ctx):
    peaks = ctx.get("peaks")
    window_read_bytes = costs.asked(ctx, "window_read_bytes")
    at = cmdaplusscopes.decode_steps(ctx)
    if window_read_bytes is None or not peaks or not at:
        return None
    step_s = dots3scopes.decode_kernel_step_seconds(
        ctx, "paged_decode_attention", "attn/window")
    if not step_s:
        return None
    nbytes = window_read_bytes(ctx["hf"], ctx["weights"], at["in_reach"],
                               at["rows"], ctx["kernels"])
    return 100.0 * nbytes / step_s / peaks["hbm_bytes_per_s"]
