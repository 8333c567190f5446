"""Memory: what of the chip's HBM the cell `cmdaplus-longctx` really holds
live (%): the parameter tree, the full layer's pages at their high
watermark AND the slot-pool entries (rings) held at the slots' high
watermark, ONE generation a slot (a ring is benign in place), over the
chip's capacity. The reader is `hbm_live_with_state_share.dots3`'s own
(it asks the configuration for a window under dots3's key; this one asks
under `sliding_window`)."""


def read(ctx):
    m, mem, peaks = ctx["engine_now"], ctx["memory"], ctx["peaks"]
    if (not peaks or not m.get("kv_total_pages") or not m.get("state_slots")
            or not mem.get("state_pool_bytes")
            or "sliding_window" not in ctx["hf"]):
        return None
    # each pool has a null entry beside what its allocator hands out
    page_bytes = mem["kv_pool_bytes"] / (m["kv_total_pages"] + 1)
    entry_bytes = mem["state_pool_bytes"] / (m["state_slots"] + 1)
    live = (mem["weights_bytes"] + m["kv_pages_watermark"] * page_bytes
            + m.get("state_slots_live", 0) * entry_bytes)
    return 100.0 * live / peaks["hbm_bytes"]
