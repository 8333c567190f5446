"""Model step: how near the chip's peak the conv and the chunked scan of
a prompt chunk run (%): the chunked scan's roofline share, kernel or
XLA. The operations the prompt tokens of a mixed step need
(`ssm_chunk_flops` of the configuration's cost module, the mean over the
flight records of the mixed steps in the traced slice, of their real
prompt tokens, not the padded bucket) times the mixed steps the trace
holds, over the device self time of scopes `attn/ssm/scan` +
`attn/ssm/conv` inside `jit_mixed_fn` (chipbench/ssmscopes.py; that time
also holds the decode rows' state update of the same step, which does
almost no arithmetic, so the share reads low by it) over the chip's peak
bf16 rate. The operations are the least the chunked form does, so the
share cannot pass 100. Bound: compute. None where the trace names no
such scope or the cost module has no answer."""
from chipbench import costs, ssmscopes


def read(ctx):
    peaks, info = ctx.get("peaks"), ctx.get("trace_info") or {}
    chunk_flops = costs.asked(ctx, "ssm_chunk_flops")
    if chunk_flops is None or not peaks or "wall_start" not in info:
        return None
    got = ssmscopes.module_seconds(ctx, ssmscopes.STATE, "jit_mixed_fn")
    mixed = [
        r for r in ctx["flight"]
        if info["wall_start"] <= r["ts"] <= info["wall_stop"]
        and r["kind"] == "mixed" and r.get("prefill_tokens")
    ]
    if not got or not got[0] or not mixed:
        return None
    seconds, count = got
    flops = sum(chunk_flops(ctx["hf"], r["prefill_tokens"])
                for r in mixed) / len(mixed)
    return 100.0 * flops * count / seconds / peaks["bf16_flops_per_s"]
