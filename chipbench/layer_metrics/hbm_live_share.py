"""Memory: what of the chip's HBM the cell really holds live: the
parameter tree plus the KV pages at their high watermark, over the
chip's capacity (%). `memory_peak_bytes` counts the whole page pool,
filled or not; this says how much of it the traffic used."""


def read(ctx):
    m, mem, peaks = ctx["engine_now"], ctx["memory"], ctx["peaks"]
    if not peaks or not m.get("kv_total_pages"):
        return None
    # the pool has one page more than the allocator hands out (the null page)
    page_bytes = mem["kv_pool_bytes"] / (m["kv_total_pages"] + 1)
    live = mem["weights_bytes"] + m["kv_pages_watermark"] * page_bytes
    return 100.0 * live / peaks["hbm_bytes"]
