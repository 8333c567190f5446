"""Model step: how near its roofline the full layers' decode attention
runs (%): the kernel `paged_decode_attention` walking EVERY page of a row's
latent cache under a bit a token (ops/paged_attention.py `latent` +
`token_bits`; models/mla.py `_latent_decode`), 128 heads. What the walk
executes for the tokens the decode rows hold (counted on the device:
`walk_pages_live` in the flight records of the traced slice's fused and
mixed dispatches): `kv_read_bytes` (1,280 B a token and full layer: the
latent and the rope key's lane tile) over the chip's peak HBM bandwidth,
and `walk_flops` (2 x 128 heads x (512 + 128 + 512) a token and layer) over
its peak bf16 FLOP/s, both of `chipbench/costs_dots3.py`; the LARGER of
the two times is the roofline (at these widths they lie 4 % apart: 230
FLOP/B against the chip's 240), over the kernel's own events under scope
`attn/paged` (the window layers walk their rings with the same kernel
under `attn/window`) a step, WHOLE dispatches of both kinds
(chipbench/dots3scopes.py). Prints the note `latent_walk_bound`, which
says which bound held. None where no such kernel ran under a window
layer's program (every other configuration, the parent commit)."""
import json

from chipbench import costs, dots3scopes


def read(ctx):
    peaks = ctx.get("peaks")
    kv_read_bytes = costs.asked(ctx, "kv_read_bytes")
    walk_flops = costs.asked(ctx, "walk_flops")
    step_s = dots3scopes.decode_kernel_step_seconds(
        ctx, "paged_decode_attention", "attn/paged")
    at = dots3scopes.decode_steps(ctx)
    if (kv_read_bytes is None or walk_flops is None or not peaks
            or not step_s or not at):
        return None
    by_bytes = (kv_read_bytes(ctx["hf"], ctx["weights"], at["live"],
                              at["rows"], ctx["kernels"])
                / peaks["hbm_bytes_per_s"])
    by_flops = walk_flops(ctx["hf"], at["live"]) / peaks["bf16_flops_per_s"]
    print(json.dumps({
        "note": "latent_walk_bound",
        "bound": "compute" if by_flops > by_bytes else "memory",
        "hbm_floor_ms": 1e3 * by_bytes, "mxu_floor_ms": 1e3 * by_flops,
        "kernel_ms_per_step": 1e3 * step_s}), flush=True)
    return 100.0 * max(by_bytes, by_flops) / step_s
