"""Model step: what a prompt chunk's latent kernel under a mask bit a
(query, key) multiplies over the MXU's peak (%): the roofline share of
`latent_prefill_attention` with `chosen` (ops/flash_prefill.py) in
dots3-note-prev's full layers. The (query, key) pairs under the causal
mask of a mixed dispatch, counted on the device by the step programs
(`chunk_pages_read` in the flight records of the traced slice's mixed
dispatches, in PAIRS a full layer: `models/keye_vl.chunk_pairs`; the kernel
computes whole tiles and turns, so it multiplies at least these), the mean
a dispatch, times `chunk_flops` of `chipbench/costs_dots3.py` (absorbed
form: 2 x 128 heads x (512 + 128 + 512) a pair), over the kernel's own
events under scope `attn/flash` (the window layers run the same kernel
under `attn/window`) a WHOLE `jit_mixed_fn` dispatch
(chipbench/dots3scopes.py), over the
chip's peak bf16 FLOP/s. Bound: compute. None where no such kernel ran
under a window layer's program (every other configuration, the parent
commit)."""
from chipbench import costs, dots3scopes


def read(ctx):
    peaks = ctx.get("peaks")
    chunk_flops = costs.asked(ctx, "chunk_flops")
    found = dots3scopes.kernel_seconds(
        ctx, "latent_prefill_attention", "jit_mixed_fn", "attn/flash")
    mixed = [r for r in dots3scopes.slice_records(ctx, "mixed")
             if r.get("chunk_pages_read")]
    if chunk_flops is None or not peaks or found is None or not mixed:
        return None
    seconds, count, _steps = found
    pairs = sum(r["chunk_pages_read"] for r in mixed) / len(mixed)
    return (100.0 * chunk_flops(ctx["hf"], pairs) / (seconds / count)
            / peaks["bf16_flops_per_s"])
