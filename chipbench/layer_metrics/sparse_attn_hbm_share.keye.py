"""Model step: how near its HBM floor the decode attention under a token
selection runs (%): the roofline share of the kernel
`paged_decode_attention` walking every page of a row under a bit a token
(models/keye_vl.py `walk_under_bits`). The K and V bytes the walk READS:
the tokens the decode rows hold, counted on the device by the step
programs (`walk_pages_live` in the flight records of the traced slice's
fused decode dispatches, in TOKENS for this family, a layer each, over
the decode steps those dispatches fused; `walk_read_bytes` of
`chipbench/costs_keye_vl.py`), over the kernel's own events inside
`jit_multi_fn` per fused decode step (chipbench/sparsescopes.py), over
the chip's peak HBM bandwidth. Bound: memory. None where no selection ran
or the cost module has no such function (every other configuration, the
parent commit)."""
from chipbench import costs, flight, sparsescopes


def read(ctx):
    peaks = ctx.get("peaks")
    walk_read_bytes = costs.asked(ctx, "walk_read_bytes")
    step_s = sparsescopes.walk_kernel_step_seconds(ctx)
    fused = sparsescopes.fused_records(ctx)
    live = sum(r.get("walk_pages_live", 0) for r in fused)
    if walk_read_bytes is None or not peaks or not step_s or not live:
        return None
    steps = sum(flight.fused_steps(r) for r in fused)
    nbytes = walk_read_bytes(ctx["hf"], ctx["weights"], live / steps)
    return 100.0 * nbytes / step_s / peaks["hbm_bytes_per_s"]
