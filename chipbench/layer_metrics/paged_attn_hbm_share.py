"""Model step: how near its HBM floor the decode page walk runs (%).
The cached K/V bytes of the live tokens of one decode step
(`kv_read_bytes` of the configuration's cost module, `ctx["costs"]`,
chipbench.costs unless its file names another: there
`kv_bytes_per_token` x live tokens; the same flight records and
half-page correction as `decode_hbm_share`) over the device self time of
scope `attn/paged` per fused decode step inside `jit_multi_fn`
(`hostspans.scope_self_s` over the `k` of the `engine.launch` spans)
over the chip's peak HBM bandwidth. The scope holds the kernel
`paged_decode_attention` and the current token's merge; the bytes are
the least the walk must read, so the share cannot pass 100. Bound:
memory. None where the trace names no `attn/paged` scope, or the cost
module has no answer."""

from chipbench import costs, flight, hostspans


def read(ctx):
    info, peaks = ctx.get("trace_info") or {}, ctx.get("peaks")
    kv_read_bytes = costs.asked(ctx, "kv_read_bytes")
    run = hostspans.of_this_run(ctx)
    if (kv_read_bytes is None or not run or not peaks
            or "wall_start" not in info):
        return None
    per_scope = hostspans.scope_self_s(run["loaded"], "jit_multi_fn")
    ks = hostspans.fused_steps(run["loaded"])
    if not per_scope or not ks or not per_scope.get("attn/paged"):
        return None
    step_s = per_scope["attn/paged"] / sum(ks)
    page = ctx["page_size"]
    fused = [
        r for r in ctx["flight"]
        if info["wall_start"] <= r["ts"] <= info["wall_stop"]
        and flight.fused_steps(r) >= 1.5
    ]
    if not fused:
        return None
    live = [max(0.0, r["active_pages"] * page - r["n_decode"] * page / 2)
            for r in fused]
    nbytes = kv_read_bytes(
        ctx["hf"], ctx["weights"], sum(live) / len(live),
        sum(r["n_decode"] for r in fused) / len(fused), ctx["kernels"])
    if nbytes is None:
        return None
    return 100.0 * nbytes / step_s / peaks["hbm_bytes_per_s"]
