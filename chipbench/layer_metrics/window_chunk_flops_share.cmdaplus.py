"""Model step: what a prompt piece's banded pass multiplies over the MXU's
peak in Command A+'s sliding layers (%): the roofline share of the kernel
`ring_prefill_attention` (ops/flash_prefill.py). The (query, key) pairs
inside the band of a mixed dispatch, counted on the device by the step
programs (`chunk_pages_read` in the flight records of the traced slice's
mixed dispatches, in PAIRS summed over the 3 sliding layers; the kernel
computes whole tiles, so it multiplies at least these), the mean a
dispatch and layer, times `pair_flops` of
`chipbench/costs_command_a_plus.py` (4 x 128 heads x 128 a pair), over the
kernel's own events under scope `attn/window` a WHOLE `jit_mixed_fn`
dispatch and layer (chipbench/dots3scopes.py), over the chip's peak bf16
FLOP/s. Bound: compute. None where no such kernel ran."""
from chipbench import cmdaplusscopes, costs, dots3scopes


def read(ctx):
    peaks = ctx.get("peaks")
    pair_flops = costs.asked(ctx, "pair_flops")
    kinds = cmdaplusscopes.layers(ctx)
    if pair_flops is None or not peaks or kinds is None:
        return None
    pairs = cmdaplusscopes.chunk_pairs(ctx, "chunk_pages_read", kinds[0])
    found = dots3scopes.kernel_seconds(
        ctx, "ring_prefill_attention", "jit_mixed_fn", "attn/window")
    if found is None or not pairs:
        return None
    seconds, count, _steps = found
    return (100.0 * pair_flops(ctx["hf"], pairs)
            / (seconds / count / kinds[0]) / peaks["bf16_flops_per_s"])
