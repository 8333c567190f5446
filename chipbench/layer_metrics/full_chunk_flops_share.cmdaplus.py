"""Model step: what a prompt piece's pass over its paged history multiplies
over the MXU's peak in Command A+'s full layer (%): the roofline share of
the kernel `ring_prefill_attention` under scope `attn/flash`
(ops/flash_prefill.py: the banded kernel with a window no position
reaches, over the row's pages gathered a KV head at a time, no rope, 8k-16k
keys of history and 128 query heads). The (query, key) pairs under the
causal mask of a mixed dispatch, counted on the device
(`chunk_pages_named` in the flight records of the traced slice's mixed
dispatches, in PAIRS a full layer), the mean a dispatch, times `pair_flops`
of `chipbench/costs_command_a_plus.py`, over the kernel's own events under
that scope a WHOLE `jit_mixed_fn` dispatch (chipbench/dots3scopes.py),
over the chip's peak bf16 FLOP/s. The gather and the transpose before the
kernel are not in its events. Bound: compute. None where no such kernel
ran under that scope."""
from chipbench import cmdaplusscopes, costs, dots3scopes


def read(ctx):
    peaks = ctx.get("peaks")
    pair_flops = costs.asked(ctx, "pair_flops")
    kinds = cmdaplusscopes.layers(ctx)
    if pair_flops is None or not peaks or kinds is None:
        return None
    pairs = cmdaplusscopes.chunk_pairs(ctx, "chunk_pages_named", kinds[1])
    found = dots3scopes.kernel_seconds(
        ctx, "ring_prefill_attention", "jit_mixed_fn", "attn/flash")
    if found is None or not pairs:
        return None
    seconds, count, _steps = found
    return (100.0 * pair_flops(ctx["hf"], pairs)
            / (seconds / count / kinds[1]) / peaks["bf16_flops_per_s"])
