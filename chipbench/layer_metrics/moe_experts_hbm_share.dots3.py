"""Model step: how near its HBM floor the routed experts' grouped matmuls
run at dots3-note-prev's shape (5120 x 1536, three matrices an expert, 8 of
256 experts held, 8 expert layers) (%): the bytes of the distinct HELD
experts a MIXED step's rows touch (`moe_experts_read_bytes` of
`chipbench/costs_dots3.py` on the program's own count, made on the device
from each layer's router choices: `moe_experts_touched` in the flight
records of the traced slice's mixed dispatches) over the device self time
of scope `mlp/moe/experts` a mixed step, WHOLE `jit_mixed_fn` dispatches
(chipbench/dots3scopes.py), over the chip's peak HBM bandwidth. A mixed
step's 500-2,000 rows make the matmuls partly compute-bound, so the share
reads under what a fused decode step's would. Bound: memory. None where
the trace names no such scope, the program counts no experts (the parent
commit) or the cost module has no answer."""
from chipbench import costs, dots3scopes


def read(ctx):
    peaks = ctx.get("peaks")
    read_bytes = costs.asked(ctx, "moe_experts_read_bytes")
    step_s = dots3scopes.step_seconds(ctx, "mlp/moe/experts")
    touched = dots3scopes.experts_touched(ctx)
    if read_bytes is None or not peaks or not step_s or not touched:
        return None
    nbytes = read_bytes(ctx["hf"], ctx["weights"], 0.0, 0.0, ctx["kernels"],
                        touched=touched)
    return 100.0 * nbytes / step_s / peaks["hbm_bytes_per_s"]
