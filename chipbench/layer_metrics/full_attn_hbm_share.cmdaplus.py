"""Model step: how near its HBM floor the FULL layer's decode attention
runs in the cell `cmdaplus-longctx` (%): the roofline share of the kernel
`paged_decode_attention` under scope `attn/paged` (the accepted GQA page
walk, q unrotated, 16 query heads a KV head). K and V of every live token
(4,096 B a token and full layer: `kv_read_bytes` of
`chipbench/costs_command_a_plus.py` on the tokens the decode rows hold,
counted on the device: `walk_pages_live` in the flight records of the
traced slice's fused and mixed dispatches) over the kernel's own events a
step, WHOLE dispatches of both kinds (chipbench/dots3scopes.py), over the
chip's peak HBM bandwidth. Bound: memory. None where no such kernel ran
under a window model's program or the program counts nothing."""
from chipbench import cmdaplusscopes, costs, dots3scopes


def read(ctx):
    peaks = ctx.get("peaks")
    kv_read_bytes = costs.asked(ctx, "kv_read_bytes")
    at = cmdaplusscopes.decode_steps(ctx)
    if kv_read_bytes is None or not peaks or not at:
        return None
    step_s = dots3scopes.decode_kernel_step_seconds(
        ctx, "paged_decode_attention", "attn/paged")
    if not step_s:
        return None
    nbytes = kv_read_bytes(ctx["hf"], ctx["weights"], at["live"], at["rows"],
                           ctx["kernels"])
    return 100.0 * nbytes / step_s / peaks["hbm_bytes_per_s"]
