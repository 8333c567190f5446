"""Model step: how near its HBM floor the routed experts' grouped matmuls
run (%). The bytes of the distinct experts one decode step's rows touch
(`moe_experts_read_bytes` of the configuration's cost module: from the
shapes, the flight records' decode rows and, where this run's reference
comparison left one, its routing probe's count of distinct experts a
layer at that many rows, `RUN_DIR/deepseek_v2_lite_routing_probe.json`;
seeded routers do not route quite evenly) over the device self time of
scope `mlp/moe/experts` per fused decode step inside `jit_multi_fn`
(chipbench/subscopes.py over hostspans' `k` of the `engine.launch`
spans) over the chip's peak HBM bandwidth. The bytes are the least the
grouped matmuls must read, so the share cannot pass 100. Bound: memory.
None where the trace names no such scope (a dense decoder, the parent
commit) or the cost module has no answer."""

import json
import os

from chipbench import costs, flight, manifest, subscopes


def probed_experts(rows: float):
    """Distinct experts a layer the served weights touch at `rows` rows,
    as this process's reference comparison measured it; None without."""
    try:
        with open(manifest.RUN_DIR
                  / "deepseek_v2_lite_routing_probe.json") as f:
            probe = json.load(f)
    except (OSError, ValueError):
        return None
    if probe.get("pid") != os.getpid() or abs(probe["rows"] - rows) > 4:
        return None
    return probe["experts_touched"]


def read(ctx):
    info, peaks = ctx.get("trace_info") or {}, ctx.get("peaks")
    read_bytes = costs.asked(ctx, "moe_experts_read_bytes")
    if read_bytes is None or not peaks or "wall_start" not in info:
        return None
    step_s = subscopes.step_seconds(ctx, "mlp/moe/experts")
    fused = [
        r for r in ctx["flight"]
        if info["wall_start"] <= r["ts"] <= info["wall_stop"]
        and flight.fused_steps(r) >= 1.5
    ]
    if not step_s or not fused:
        return None
    rows = sum(r["n_decode"] for r in fused) / len(fused)
    nbytes = read_bytes(ctx["hf"], ctx["weights"], 0.0, rows, ctx["kernels"],
                        touched=probed_experts(rows))
    if nbytes is None:
        return None
    return 100.0 * nbytes / step_s / peaks["hbm_bytes_per_s"]
