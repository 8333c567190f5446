"""Engine loop, `qwen2-longgen`: the p95 of the gaps between deliveries
at the client (ms a token, as `itl_p95_ms` counts them: every gap that
ended inside the window). Here it is a per-layer reading and no
end-to-end metric: half of this cell's deliveries are admissions (a
mixed step with 40-55 ms of idle chip about it), so the p95 sits in the
host's timing of those and repeats to 5-9 % from run to run, which no
bound the contract allows can hold (PERF.md 6, PR 26)."""
from chipbench import stats


def read(ctx):
    gaps = ctx["client"]["gaps_ms"]
    return stats.percentile(gaps, 95) if gaps else None
