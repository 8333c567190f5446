"""Model step: what a prompt chunk's token-mask kernel multiplies over
the MXU's peak (%): the roofline share of `token_chunk_attention`
(ops/sparse_chunk.py). The (query, key) pairs under the causal mask of
the traced slice's mixed dispatches, counted on the device by the step
programs (`chunk_pages_read` in their flight records, in PAIRS a layer
for this family: `models/keye_vl.chunk_pairs`; the kernel computes whole
tiles and turns, so it multiplies at least these), times `chunk_flops` of
`chipbench/costs_keye_vl.py`, over the kernel's own events inside
`jit_mixed_fn` (chipbench/indexscopes.py), over the chip's peak bf16
FLOP/s. Bound: compute. None where no such kernel ran (every other
configuration, the parent commit)."""
from chipbench import costs, indexscopes


def read(ctx):
    peaks = ctx.get("peaks")
    chunk_flops = costs.asked(ctx, "chunk_flops")
    seconds = indexscopes.kernel_seconds(
        ctx, "token_chunk_attention", "jit_mixed_fn")
    pairs = sum(r.get("chunk_pages_read", 0)
                for r in indexscopes.slice_records(ctx))
    if chunk_flops is None or not peaks or not seconds or not pairs:
        return None
    return (100.0 * chunk_flops(ctx["hf"], pairs) / seconds
            / peaks["bf16_flops_per_s"])
