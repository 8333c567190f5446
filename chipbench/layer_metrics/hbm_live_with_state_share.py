"""Memory: what of the chip's HBM a cell with a recurrent-state pool
really holds live (%): the parameter tree, the KV pages at their high
watermark AND the state-pool entries held at the slots' high watermark
(both generations of every slot held: a dispatch launched ahead writes
the other one), over the chip's capacity. It is what `hbm_live_share`
under-reads by in a state model: that metric counts weights and pages
alone. Cannot pass 100: every term is part of what the device holds.
None for a program or a configuration without a state pool."""


def read(ctx):
    m, mem, peaks = ctx["engine_now"], ctx["memory"], ctx["peaks"]
    if (not peaks or not m.get("kv_total_pages") or not m.get("state_slots")
            or not mem.get("state_pool_bytes")):
        return None
    # each pool has a null entry beside what its allocator hands out: one
    # page, and one slot a generation
    page_bytes = mem["kv_pool_bytes"] / (m["kv_total_pages"] + 1)
    entry_bytes = mem["state_pool_bytes"] / (2 * (m["state_slots"] + 1))
    live = (mem["weights_bytes"] + m["kv_pages_watermark"] * page_bytes
            + 2 * m.get("state_slots_live", 0) * entry_bytes)
    return 100.0 * live / peaks["hbm_bytes"]
