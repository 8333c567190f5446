"""Model step: how near its HBM floor the SELECTING page walk runs (%):
the roofline share of the kernel `paged_decode_attention` walking a list
of at most 64 chosen pages a (row, KV head). The K and V bytes of the
pages the lists NAMED, counted on the device by the step programs
(`walk_pages_named` in the flight records of the traced slice's fused
decode dispatches, a page a KV head and a sparse layer, over the decode
steps those dispatches fused; `walk_bytes` of
`chipbench/costs_minicpm_sala.py`), over the kernel's own events inside
`jit_multi_fn` per fused decode step (chipbench/sparsescopes.py), over
the chip's peak HBM bandwidth. Bound: memory. None where no selecting
walk ran or the program has no such counter (every other configuration,
the parent commit)."""
from chipbench import costs, flight, sparsescopes


def read(ctx):
    peaks = ctx.get("peaks")
    walk_bytes = costs.asked(ctx, "walk_bytes")
    step_s = sparsescopes.walk_kernel_step_seconds(ctx)
    fused = sparsescopes.fused_records(ctx)
    named = sum(r.get("walk_pages_named", 0) for r in fused)
    if walk_bytes is None or not peaks or not step_s or not named:
        return None
    steps = sum(flight.fused_steps(r) for r in fused)
    nbytes = walk_bytes(ctx["hf"], ctx["weights"], named / steps)
    return 100.0 * nbytes / step_s / peaks["hbm_bytes_per_s"]
