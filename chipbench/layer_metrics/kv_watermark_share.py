"""Memory (PageAllocator.watermark): high watermark of active KV pages
over the pool (%)."""


def read(ctx):
    m = ctx["engine_now"]
    if not m.get("kv_total_pages"):
        return None
    return 100.0 * m["kv_pages_watermark"] / m["kv_total_pages"]
