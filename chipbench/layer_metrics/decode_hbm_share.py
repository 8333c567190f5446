"""Model step (models/llama.py step programs): the bytes a fused decode
dispatch had to read (weights once a step plus the cached K/V of every
live token, from shapes: `step_read_bytes` of the configuration's cost
module, `ctx["costs"]`, chipbench.costs unless its file names another;
None from it leaves the metric out) over the device time of one
(`jit_multi_fn` in the trace: seconds over count) over the chip's peak
HBM bandwidth (%). Bound: memory.

Time and count are the trace's own. Bytes are the mean over the flight
recorder's fused dispatches about the traced slice (wall clock: which
records, not how many, so an edge does no harm): live tokens are the
active pages, less half a page per row for the partly filled last page;
a record fused at least two steps if it emitted more than 1.5 tokens a
row (single steps share `jit_step_fn` with prefill and are left out of
both sides). Rows that finish mid-dispatch drop their overshoot, so the
fused count, and with it the share, reads a little low."""

from chipbench import costs, flight


def read(ctx):
    tr, info, peaks = ctx["trace"], ctx["trace_info"], ctx["peaks"]
    if not tr or not peaks or "wall_start" not in info:
        return None
    dev = tr["modules"].get("jit_multi_fn")
    if not dev or not dev["seconds"]:
        return None
    step_read_bytes = costs.asked(ctx, "step_read_bytes")
    if step_read_bytes is None:
        return None
    w = ctx["weights"]
    page = ctx["page_size"]
    per_dispatch = []
    for r in ctx["flight"]:
        if not info["wall_start"] <= r["ts"] <= info["wall_stop"]:
            continue
        k = flight.fused_steps(r)
        if k < 1.5:
            continue
        live = max(0.0, r["active_pages"] * page - r["n_decode"] * page / 2)
        nbytes = step_read_bytes(ctx["hf"], w, live, r["n_decode"],
                                 ctx["kernels"])
        if nbytes is None:
            return None
        per_dispatch.append(k * nbytes)
    if not per_dispatch:
        return None
    nbytes = sum(per_dispatch) / len(per_dispatch)
    return (100.0 * nbytes * dev["count"] / dev["seconds"]
            / peaks["hbm_bytes_per_s"])
