"""Model step: how near its HBM floor the indexer's scoring runs (%): its
roofline share. The index-key bytes of the decode rows' contexts (one
64-wide bf16 key a cached token and layer: `index_read_bytes` of
`chipbench/costs_keye_vl.py` on the live tokens of the traced slice's
fused decode dispatches, as `paged_attn_hbm_share` counts them) over the
device self time of scope `attn/index` per fused decode step inside
`jit_multi_fn` (chipbench/indexscopes.py) over the chip's peak HBM
bandwidth. The bytes are the least the scores must read, so the share
cannot pass 100. Bound: memory. None where the trace names no such scope
or the cost module has no answer (every other configuration, the parent
commit)."""
from chipbench import costs, indexscopes, ssmscopes


def read(ctx):
    peaks = ctx.get("peaks")
    index_read_bytes = costs.asked(ctx, "index_read_bytes")
    step_s = indexscopes.step_seconds(ctx, "attn/index")
    fused = ssmscopes.fused_records(ctx)
    if index_read_bytes is None or not peaks or not step_s or not fused:
        return None
    page = ctx["page_size"]
    live = [max(0.0, r["active_pages"] * page - r["n_decode"] * page / 2)
            for r in fused]
    nbytes = index_read_bytes(
        ctx["hf"], ctx["weights"], sum(live) / len(live),
        sum(r["n_decode"] for r in fused) / len(fused), ctx["kernels"])
    return 100.0 * nbytes / step_s / peaks["hbm_bytes_per_s"]
