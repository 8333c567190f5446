"""Memory: what of the chip's HBM the cell `dots3-longctx` really holds
live (%): the parameter tree, the full layers' pages at their high
watermark AND the slot-pool entries (rings) held at the slots' high
watermark, over the chip's capacity. A window layer's ring is benign in
place, so the pool keeps ONE generation a slot (`hbm_live_with_state_share`
counts two, a recurrent state's). Cannot pass 100: every term is part of
what the device holds. None for a program or a configuration without a
slot pool, or one whose slots keep two generations (no `sliding_window_size`
in the configuration)."""


def read(ctx):
    m, mem, peaks = ctx["engine_now"], ctx["memory"], ctx["peaks"]
    if (not peaks or not m.get("kv_total_pages") or not m.get("state_slots")
            or not mem.get("state_pool_bytes")
            or "sliding_window_size" not in ctx["hf"]):
        return None
    # each pool has a null entry beside what its allocator hands out
    page_bytes = mem["kv_pool_bytes"] / (m["kv_total_pages"] + 1)
    entry_bytes = mem["state_pool_bytes"] / (m["state_slots"] + 1)
    live = (mem["weights_bytes"] + m["kv_pages_watermark"] * page_bytes
            + m.get("state_slots_live", 0) * entry_bytes)
    return 100.0 * live / peaks["hbm_bytes"]
