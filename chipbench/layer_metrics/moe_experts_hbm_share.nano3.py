"""Model step: how near its HBM floor the routed experts' grouped matmuls
run at Nemotron-H's shape (2688 x 1856 stored as 1920, two matrices an
expert, 16 of 128 experts held) (%). The bytes of the distinct HELD
experts one decode step's rows touch (`moe_experts_read_bytes` of the
configuration's cost module: from the shapes as stored, the flight
records' decode rows and, where this run's reference comparison left
one, its routing probe's count of distinct held experts a layer,
`RUN_DIR/nemotron_h_routing_probe.json`) over the device self time of
scope `mlp/moe/experts` per fused decode step inside `jit_multi_fn`
(chipbench/subscopes.py) over the chip's peak HBM bandwidth. The bytes
are the least the grouped matmuls must read, so the share cannot pass
100. Bound: memory. A metric of its own beside `moe_experts_hbm_share`
because the probe's file and the count of matrices are this
configuration's. None where the trace names no such scope or the cost
module has no answer."""
import json
import os

from chipbench import costs, manifest, ssmscopes, subscopes


def probed_experts(rows: float):
    """Distinct held experts a layer the served weights touch at `rows`
    rows, as this process's reference comparison measured it."""
    try:
        with open(manifest.RUN_DIR / "nemotron_h_routing_probe.json") as f:
            probe = json.load(f)
    except (OSError, ValueError):
        return None
    if probe.get("pid") != os.getpid() or abs(probe["rows"] - rows) > 4:
        return None
    return probe["experts_touched"]


def read(ctx):
    peaks = ctx.get("peaks")
    read_bytes = costs.asked(ctx, "moe_experts_read_bytes")
    if read_bytes is None or not peaks:
        return None
    step_s = subscopes.step_seconds(ctx, "mlp/moe/experts")
    fused = ssmscopes.fused_records(ctx)
    if not step_s or not fused:
        return None
    rows = sum(r["n_decode"] for r in fused) / len(fused)
    nbytes = read_bytes(ctx["hf"], ctx["weights"], 0.0, rows, ctx["kernels"],
                        touched=probed_experts(rows))
    if nbytes is None:
        return None
    return 100.0 * nbytes / step_s / peaks["hbm_bytes_per_s"]
