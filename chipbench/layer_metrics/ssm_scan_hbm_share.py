"""Model step: how near its HBM floor the recurrent state's update runs
(%): the decode kernel's roofline share. The bytes one decode step has
to move for the state (`ssm_state_bytes` of the configuration's cost
module: every live row's SSM state and conv window of every Mamba-2
layer, read once and written once; rows from the flight records of the
traced slice) over the device self time of scopes `attn/ssm/scan` +
`attn/ssm/conv` per fused decode step inside `jit_multi_fn`
(chipbench/ssmscopes.py) over the chip's peak HBM bandwidth. The bytes
are the least the update must move, so the share cannot pass 100. Bound:
memory. None where the trace names no such scope or the cost module has
no answer."""
from chipbench import costs, ssmscopes


def read(ctx):
    peaks = ctx.get("peaks")
    state_bytes = costs.asked(ctx, "ssm_state_bytes")
    if state_bytes is None or not peaks:
        return None
    step_s = ssmscopes.step_seconds(ctx, *ssmscopes.STATE)
    fused = ssmscopes.fused_records(ctx)
    if not step_s or not fused:
        return None
    rows = sum(r["n_decode"] for r in fused) / len(fused)
    nbytes = state_bytes(ctx["hf"], ctx["weights"], 0.0, rows, ctx["kernels"])
    if nbytes is None:
        return None
    return 100.0 * nbytes / step_s / peaks["hbm_bytes_per_s"]
