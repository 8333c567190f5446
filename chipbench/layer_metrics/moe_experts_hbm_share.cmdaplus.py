"""Model step: how near its HBM floor the routed experts' grouped matmuls
run at Command A+'s shape (4096 x 4096, three matrices an expert, 16 of
128 experts held, 4 layers) (%): the bytes of the distinct HELD experts a
MIXED step's rows chose (`moe_experts_read_bytes` of
`chipbench/costs_command_a_plus.py` on the program's own count, made on
the device from each layer's router choices: `moe_experts_touched` in the
flight records of the traced slice's mixed dispatches) over the device self
time of scope `mlp/moe/experts` a mixed step, WHOLE `jit_mixed_fn`
dispatches (chipbench/dots3scopes.py), over the chip's peak HBM bandwidth.
Bound: memory. None where the trace names no such scope, the program counts
no experts or the cost module has no answer."""
from chipbench import cmdaplusscopes, costs, dots3scopes


def read(ctx):
    peaks = ctx.get("peaks")
    read_bytes = costs.asked(ctx, "moe_experts_read_bytes")
    if read_bytes is None or not peaks or cmdaplusscopes.layers(ctx) is None:
        return None
    step_s = dots3scopes.step_seconds(ctx, "mlp/moe/experts")
    touched = dots3scopes.experts_touched(ctx)
    if not step_s or not touched:
        return None
    nbytes = read_bytes(ctx["hf"], ctx["weights"], 0.0, 0.0, ctx["kernels"],
                        touched=touched)
    return 100.0 * nbytes / step_s / peaks["hbm_bytes_per_s"]
