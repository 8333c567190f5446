"""Model step: how near its HBM floor the indexer's scoring runs in the
cell `dots3-longctx` (%): the roofline share of the kernel
`paged_index_scores`. The index-key bytes of the decode rows' contexts
(one 128-wide bf16 key, 256 B, a cached token and full layer:
`index_read_bytes` of `chipbench/costs_dots3.py` on the tokens the decode
rows hold, counted on the device: `walk_pages_live` in the flight records
of the traced slice's fused and mixed dispatches) over the kernel's own
events under scope `attn/index` a step, WHOLE dispatches of both kinds
(chipbench/dots3scopes.py), over the chip's peak HBM bandwidth. The bytes
are the least the scores must read, so the share cannot pass 100. Bound:
memory. None where no such kernel ran or the cost module has no answer
(every other configuration, the parent commit)."""
from chipbench import costs, dots3scopes


def read(ctx):
    peaks = ctx.get("peaks")
    index_read_bytes = costs.asked(ctx, "index_read_bytes")
    step_s = dots3scopes.decode_kernel_step_seconds(
        ctx, "paged_index_scores", "attn/index")
    at = dots3scopes.decode_steps(ctx)
    if index_read_bytes is None or not peaks or not step_s or not at:
        return None
    nbytes = index_read_bytes(ctx["hf"], ctx["weights"], at["live"],
                              at["rows"], ctx["kernels"])
    return 100.0 * nbytes / step_s / peaks["hbm_bytes_per_s"]
